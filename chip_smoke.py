"""Bring-up check: the decentralized training step and its Pallas consensus
kernels on a TPU.

One chip (the default) runs the paper's own job at its published size
(``repro.configs.resnet20_cifar``: ResNet-20 at width 16, 10 classes, 32x32
images, K=16 agents stacked on the chip, per-agent batch 128, ring, DRT,
3 consensus rounds) through ``DecentralizedTrainer`` with random weights and
CIFAR-like data made from a seed, in four phases:

  a  ``use_kernels=False``, exact exchange (the jnp reference)
  b  ``use_kernels=True``, exact exchange
  c  ``use_kernels=True``, int8 wire, dense slab rounds
  d  ``use_kernels=True``, int8 wire, sparse edge rounds

Each phase compiles the jitted ``epoch`` (4 minibatches, then the round-set)
and takes two epochs; the losses must be finite, and the last epoch's mean
loss must lie below the loss of the initial parameters.  For b-d the
kernel round-set, run on the state after the first epoch's local steps,
must match the jnp round-set within 1e-5, must lower the disagreement, and
its compiled program must hold a Mosaic kernel (``tpu_custom_call``).  Then the LM CLI
(``repro.launch.train.main``) takes 3 steps on a ``-smoke`` arch.

``--four-chips`` runs only the one-agent-per-chip path: K=4 agents on a
``(4,)`` data mesh, each an ``h2o-danube-3-4b`` at its published widths cut
to one layer, ring, ``use_kernels=True``.  The ``permute`` engine (neighbour
exchange over ICI) is compared with the ``gather`` engine on the same mesh,
for the exact and the int8 exchange, within 1e-5.

Every timing printed is a bring-up timing, not a benchmark number.  The last
line of standard output is one JSON object naming the device.  The script
exits non-zero, printing no such line, when JAX finds no TPU or any check
fails.  Run it from the root of a checkout:

    python3 chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

TOL = 1e-5  # kernel vs jnp round-set, permute vs gather engine
PAPER_PHASES = (
    ("a jnp exact", dict(use_kernels=False)),
    ("b kernels exact", dict(use_kernels=True)),
    ("c kernels int8 slab", dict(use_kernels=True, codec="int8", consensus_path="slab")),
    ("d kernels int8 edge", dict(use_kernels=True, codec="int8", consensus_path="edge")),
)


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def max_abs_diff(a, b) -> float:
    import jax
    import jax.numpy as jnp

    return max(
        float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# one chip: the paper job
# ---------------------------------------------------------------------------


def paper_trainer(cfg, rounds=None, **tcfg):
    """The paper job's trainer; ``rounds`` overrides its 3 consensus rounds."""
    from repro.core import DecentralizedTrainer, DRTConfig, TrainerConfig, ring
    from repro.models.resnet import init_resnet20, resnet20_loss
    from repro.optim import momentum

    return DecentralizedTrainer(
        lambda p, b, rng: resnet20_loss(p, b),
        lambda key: init_resnet20(key, width=cfg.width, num_classes=cfg.num_classes),
        momentum(cfg.lr, cfg.momentum),
        ring(cfg.num_agents),
        TrainerConfig(
            algorithm="drt",
            consensus_steps=cfg.consensus_steps if rounds is None else rounds,
            drt=DRTConfig(N=cfg.drt_N),
            **tcfg,
        ),
    )


def paper_batches(cfg, n_minibatches: int, epochs: int, seed: int):
    """Per-epoch CIFAR-like minibatch stacks, leading (n_minibatches, K)."""
    import jax.numpy as jnp

    from repro.data import CifarLike, CifarLikeConfig, agent_minibatches

    data = CifarLike(
        CifarLikeConfig(
            num_classes=cfg.num_classes, image_size=cfg.image_size, seed=seed
        )
    )
    shards = data.paper_partition(
        num_agents=cfg.num_agents,
        min_classes=cfg.min_classes_per_agent,
        max_classes=cfg.max_classes_per_agent,
        min_samples=cfg.min_samples_per_agent,
        max_samples=cfg.max_samples_per_agent,
        seed=seed + 1,
    )
    out = []
    for e in range(epochs):
        b = agent_minibatches(shards, batch_size=cfg.batch_size, epoch_seed=e)
        out.append(
            {k: jnp.asarray(v[:n_minibatches]) for k, v in b.items()}
        )
    return out


def run_paper_phases(cfg, *, n_minibatches: int = 4, epochs: int = 2, seed: int = 0):
    """Phases a-d of the module docstring; raises SystemExit on a failed
    check.  Returns the per-phase records."""
    import jax

    from repro.core.packing import build_slab_layout

    device = jax.devices()[0]
    batches = paper_batches(cfg, n_minibatches, epochs, seed)
    records = []
    for name, tcfg in PAPER_PHASES:
        tr = paper_trainer(cfg, **tcfg)
        st0 = tr.init(jax.random.key(seed))
        template = jax.eval_shape(tr.init_fn, jax.random.key(0))
        D = build_slab_layout(tr.partition, template).D
        n_params = sum(math.prod(x.shape) for x in jax.tree.leaves(template))

        t0 = time.perf_counter()
        epoch = jax.jit(tr.epoch).lower(st0, batches[0], jax.random.key(1)).compile()
        compile_s = time.perf_counter() - t0
        # the loss before training (the first step's, on its minibatch), then
        # each epoch's mean
        first = jax.tree.map(lambda x: x[0], batches[0])
        loss0 = jax.jit(jax.vmap(lambda p, b: tr.loss_fn(p, b, None)))(
            st0.params, first
        )
        st, losses, step_s, dis = st0, [float(loss0.mean())], [], []
        for e in range(epochs):
            t0 = time.perf_counter()
            st, m = epoch(st, batches[e], jax.random.key(100 + e))
            losses.append(float(m["loss"]))  # waits for the epoch
            step_s.append(time.perf_counter() - t0)
            dis.append(float(m["disagreement"]))
        check(all(math.isfinite(x) for x in losses), f"{name}: losses {losses}")
        check(losses[-1] < losses[0], f"{name}: loss did not fall: {losses}")

        rec = dict(
            phase=name, D=D, params_per_agent=n_params,
            slab_bytes=cfg.num_agents * D * 4,
            compile_s=compile_s, epoch_s=step_s, losses=losses,
            disagreement=dis, epoch_has_kernel=has_kernel(epoch),
        )
        if tcfg["use_kernels"]:
            rec.update(compare_roundsets(cfg, tr, st0, batches[0], name, tcfg, seed))
        rec["peak_bytes_in_use"] = peak_bytes(device)
        log(f"phase {name}: {json.dumps(rec)}")
        records.append(rec)
    return records


def local_phase(tr, state, batches, key):
    """The local steps of ``tr.epoch`` without its round-set: the state the
    epoch's round-set starts from."""
    import jax

    n = jax.tree.leaves(batches)[0].shape[0]

    def body(st, inp):
        st, _ = tr.local_step(st, *inp)
        return st, None

    state, _ = jax.lax.scan(body, state, (batches, jax.random.split(key, n)))
    return state


def compare_roundsets(cfg, tr, st0, batches, name, tcfg, seed):
    """Kernel vs jnp round-set on the state after the first epoch's local
    steps from ``st0``.  Each round of the set is compared on the same
    input, and the exact exchange's whole set too.  A coded
    (stochastic-rounding) wire turns a last-bit difference between two
    rounds' outputs into a whole quantization step in the next round's
    rounding, so over a coded set only the per-round comparison is a kernel
    check; its whole-set difference is reported."""
    import jax

    def consensus(rounds, **kw):
        t = paper_trainer(cfg, rounds=rounds, **dict(tcfg, **kw))
        t.init(jax.random.key(seed))  # builds its slab layout
        return jax.jit(t.consensus)

    post = jax.jit(functools.partial(local_phase, tr))(
        st0, batches, jax.random.key(1)
    )
    t0 = time.perf_counter()
    roundset = jax.jit(tr.consensus).lower(post).compile()
    rec = dict(roundset_compile_s=time.perf_counter() - t0)
    got, _ = roundset(post)
    want, _ = consensus(None, use_kernels=False)(post)
    one_kernel, one_jnp = consensus(1), consensus(1, use_kernels=False)
    per_round, x = [], post
    for _ in range(cfg.consensus_steps):
        a, _ = one_kernel(x)
        b, _ = one_jnp(x)
        per_round.append(max_abs_diff(a.params, b.params))
        x = a
    before = float(tr.disagreement(post.params))
    after = float(tr.disagreement(got.params))
    rec.update(
        roundset_has_kernel=has_kernel(roundset),
        per_round_max_abs_diff=per_round,
        roundset_max_abs_diff=max_abs_diff(got.params, want.params),
        disagreement_before=before, disagreement_after=after,
    )
    check(rec["roundset_has_kernel"], f"{name}: no tpu_custom_call in the round-set")
    check(max(per_round) <= TOL, f"{name}: kernel vs jnp rounds differ by {per_round}")
    if tcfg.get("codec") is None:
        err = rec["roundset_max_abs_diff"]
        check(err <= TOL, f"{name}: kernel vs jnp round-set differ by {err}")
    check(after < before, f"{name}: disagreement {before} -> {after}")
    return rec


def run_lm_cli(arch: str = "qwen3-8b-smoke") -> float:
    from repro.launch.train import main as train_main

    t0 = time.perf_counter()
    train_main(["--arch", arch, "--agents", "4", "--steps", "3",
                "--batch", "2", "--seq", "32"])
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# four chips: one agent per chip, permute vs gather engine
# ---------------------------------------------------------------------------

FOUR_CHIP_ARCH = "h2o-danube-3-4b"


def four_chip_config():
    """h2o-danube-3-4b at its published widths, cut to ONE decoder layer
    (one whole period of its uniform attn+MLP pattern), K=4 agents."""
    from repro.models.registry import get_config

    full = get_config(FOUR_CHIP_ARCH)
    groups = tuple(dataclasses.replace(g, repeat=1) for g in full.groups)
    return dataclasses.replace(full, groups=groups, num_agents=4)


def four_chip_setup(mesh):
    """The pieces of the one-agent-per-chip step on the ``data`` mesh:
    ``(cfg, bundle, optimizer, local_step, roundsets)``, where
    ``roundsets(codec)`` gives the ``(permute, gather)`` round-set functions.

    A full step does not fit: at ~401M f32 parameters per agent each copy is
    1.6 GB, and the permute engine's round holds the self, received and
    decoded slabs next to the parameters, gradients and optimizer state.  So
    the two engines are compared through the round-set alone, on the same
    post-local-step parameters.  The gather engine runs its jnp slab path:
    Mosaic kernels cannot be partitioned automatically over the sharded
    agent axis, and only the permute engine runs inside ``shard_map``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import DRTConfig, ring
    from repro.core.consensus import PermuteConsensus, gather_consensus_rounds
    from repro.core.packing import build_slab_layout
    from repro.launch.train import build_partition
    from repro.models.registry import build_bundle
    from repro.optim import momentum

    cfg = four_chip_config()
    bundle = build_bundle(cfg)
    topo = ring(cfg.num_agents)
    opt = momentum(0.05, 0.9)
    drt = DRTConfig()
    part = build_partition(bundle)
    p1 = jax.eval_shape(bundle.init, jax.random.key(0))
    layout = build_slab_layout(part, p1)
    specs = jax.tree.map(lambda _: P("data"), p1)

    def local_step(state, batch_K, key):
        keys = jax.random.split(key, cfg.num_agents)
        grads = jax.vmap(jax.grad(bundle.loss))(state.params, batch_K, keys)
        params, _ = opt.update(grads, state.opt_state, state.params, state.step)
        return params

    def roundsets(codec):
        engine = PermuteConsensus(
            part, topo, drt, axis_name="data", codec=codec, use_kernels=True
        )

        def permute_rounds(params, key):
            def body(local, k):
                sq = jax.tree.map(lambda x: x[0], local)
                out = engine(sq, rng=k)[0] if codec is not None else engine(sq)
                return jax.tree.map(lambda x: x[None], out)

            return jax.shard_map(
                body, mesh=mesh, in_specs=(specs, P()), out_specs=specs,
                check_vma=False,
            )(params, key)

        def gather_rounds(params, key):
            new, _, _ = gather_consensus_rounds(
                part, params, jnp.asarray(topo.c_matrix(), jnp.float32), drt,
                codec=codec, rng=key, layout=layout,
                metropolis=jnp.asarray(topo.metropolis(), jnp.float32),
            )
            return new

        return permute_rounds, gather_rounds

    return cfg, bundle, opt, local_step, roundsets


def run_four_chips(*, seq: int = 512, batch: int = 1, seed: int = 0):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.launch.train import init_train_state

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, JAX sees {len(devices)}")
    mesh = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    shard, replicated = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    cfg, bundle, opt, local_step, roundsets = four_chip_setup(mesh)
    K = cfg.num_agents
    state = init_train_state(bundle, opt, jax.random.key(seed))
    state = jax.device_put(
        state, jax.tree.map(lambda x: shard if x.ndim else replicated, state)
    )
    tokens = jax.random.randint(
        jax.random.key(seed + 1), (K, batch, seq + 1), 0, cfg.vocab
    )
    batch_K = jax.device_put({"tokens": tokens}, shard)
    post = jax.jit(local_step)(state, batch_K, jax.random.key(seed + 2))
    del state
    key = jax.random.key(seed + 3)
    records = []
    for codec in (None, "int8"):
        permute_rounds, gather_rounds = roundsets(codec)
        t0 = time.perf_counter()
        permute = jax.jit(permute_rounds).lower(post, key).compile()
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        got = jax.block_until_ready(permute(post, key))
        rounds_s = time.perf_counter() - t0
        # to the host: the int8 gather program needs 11.72 GB of a chip's
        # HBM, more than is free next to a second 1.6 GB-per-chip result
        got = jax.device_get(got)
        t0 = time.perf_counter()
        want = jax.device_get(jax.jit(gather_rounds)(post, key))
        gather_s = time.perf_counter() - t0
        err = max(
            float(np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))))
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))
        )
        rec = dict(
            codec=codec or "exact",
            params_per_agent=sum(
                math.prod(x.shape[1:]) for x in jax.tree.leaves(post)
            ),
            permute_compile_s=compile_s, permute_roundset_s=rounds_s,
            gather_compile_and_roundset_s=gather_s,
            permute_has_kernel=has_kernel(permute),
            permute_vs_gather_max_abs_diff=err,
            peak_bytes_in_use=[peak_bytes(d) for d in devices],
        )
        log(f"four chips {rec['codec']}: {json.dumps(rec)}")
        check(rec["permute_has_kernel"], f"four chips {codec}: no tpu_custom_call")
        check(err <= TOL, f"four chips {codec}: permute vs gather differ by {err}")
        records.append(rec)
        del got, want
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the one-agent-per-chip permute vs gather phase (4 chips)",
    )
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX sees {devices[0].platform} devices"
        )
    from repro.configs.resnet20_cifar import PAPER
    from repro.utils.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    if args.four_chips:
        run_four_chips()
    else:
        run_paper_phases(PAPER)
        log(f"lm cli: 3 steps in {run_lm_cli():.3f} s (bring-up timing)")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
