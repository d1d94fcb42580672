import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
).strip()

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x mesh)
combination against the production mesh, with ShapeDtypeStruct inputs (no
allocation), and extract memory / cost / collective analyses for §Roofline.

The two lines above MUST stay first: jax locks the device count on first init.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results.json
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, SHAPES, applicable
from repro.configs.shapes import InputShape
from repro.core.decentralized import TrainerConfig
from repro.core.topology import make_topology
from repro.launch import sharding as shr
from repro.launch.mesh import make_production_mesh, mesh_axis_sizes
from repro.launch.roofline import RooflineReport, model_flops, parse_collective_bytes
from repro.launch.specs import decode_state_specs, input_specs
from repro.launch.train import abstract_train_state, make_train_step
from repro.models.registry import get_bundle
from repro.optim import momentum


def _opt_pspecs(opt_state_abstract, params_pspecs):
    """Optimizer state mirrors param sharding (elementwise transforms)."""

    def like(sub):
        return jax.tree.map(
            lambda _, s: s, sub, params_pspecs, is_leaf=lambda x: isinstance(x, P)
        )

    if isinstance(opt_state_abstract, dict):  # momentum/adam: {'m': tree, ...}
        return {k: like(v) for k, v in opt_state_abstract.items()}
    return jax.tree.map(lambda _: P(), opt_state_abstract)


def lower_train(
    bundle,
    mesh,
    shape: InputShape,
    algorithm: str = "drt",
    consensus_impl: str = "gather",
    exchange_dtype=None,
    codec=None,
):
    cfg = bundle.cfg
    topo = make_topology("ring", cfg.num_agents)
    opt = momentum(1e-2, 0.9)
    tcfg = TrainerConfig(algorithm=algorithm)

    state = abstract_train_state(bundle, opt, codec=codec)
    batch = input_specs(cfg, shape)
    p_specs = shr.param_pspecs(cfg, state.params, mesh, with_agents=True)
    step = make_train_step(
        bundle,
        topo,
        opt,
        tcfg,
        consensus_rounds=1,
        consensus_impl=consensus_impl,
        exchange_dtype=exchange_dtype,
        codec=codec,
        mesh=mesh,
        param_specs=p_specs,
    )
    o_specs = _opt_pspecs(state.opt_state, p_specs)
    b_specs = shr.train_batch_pspecs(cfg, batch, mesh)
    # codec state mirrors the agent-stacked params -> same sharding
    c_specs = (
        () if state.comm == ()
        else jax.tree.map(lambda _, s: s, state.comm, p_specs,
                          is_leaf=lambda x: isinstance(x, P))
    )
    state_specs = type(state)(p_specs, o_specs, P(), c_specs)
    in_shardings = (
        jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs,
                     is_leaf=lambda x: isinstance(x, P)),
        jax.tree.map(lambda s: NamedSharding(mesh, s), b_specs,
                     is_leaf=lambda x: isinstance(x, P)),
        NamedSharding(mesh, P()),
    )
    out_shardings = (in_shardings[0], NamedSharding(mesh, P()))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)

    def fn(state, batch, key_data):
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        new_state, metrics = step(state, batch, key)
        return new_state, metrics["loss"]

    lowered = jax.jit(fn, in_shardings=in_shardings, out_shardings=out_shardings).lower(
        state, batch, key
    )
    return lowered


def lower_prefill(bundle, mesh, shape: InputShape):
    cfg = bundle.cfg
    batch = input_specs(cfg, shape)
    p1 = jax.eval_shape(bundle.init, jax.random.key(0))
    p_specs = shr.param_pspecs(cfg, p1, mesh, with_agents=False)
    b_specs = shr.serve_batch_pspecs(batch, mesh)
    in_shardings = (
        jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                     is_leaf=lambda x: isinstance(x, P)),
        jax.tree.map(lambda s: NamedSharding(mesh, s), b_specs,
                     is_leaf=lambda x: isinstance(x, P)),
    )

    max_len = shape.seq_len if cfg.family != "vlm" else shape.seq_len
    def fn(params, batch):
        return bundle.prefill(params, batch, max_len)

    lowered = jax.jit(fn, in_shardings=in_shardings).lower(p1, batch)
    return lowered


def lower_decode(bundle, mesh, shape: InputShape):
    cfg = bundle.cfg
    token, caches, pos = decode_state_specs(cfg, shape)
    p1 = jax.eval_shape(bundle.init, jax.random.key(0))
    p_specs = shr.param_pspecs(cfg, p1, mesh, with_agents=False)
    c_specs = shr.cache_pspecs(cfg, caches, mesh, shape.global_batch)
    t_spec = shr.serve_batch_pspecs(token, mesh)
    named = lambda t: jax.tree.map(
        lambda s: NamedSharding(mesh, s), t, is_leaf=lambda x: isinstance(x, P)
    )
    in_shardings = (named(p_specs), named(t_spec), named(c_specs), NamedSharding(mesh, P()))
    out_shardings = (NamedSharding(mesh, P()), named(c_specs))  # logits replicated

    def fn(params, token, caches, pos):
        return bundle.decode_step(params, token, caches, pos)

    lowered = jax.jit(fn, in_shardings=in_shardings, out_shardings=out_shardings).lower(
        p1, token, caches, pos
    )
    return lowered


def run_one(arch: str, shape_name: str, multi_pod: bool, algorithm: str = "drt",
            consensus_impl: str = "gather", exchange_dtype=None, codec=None,
            variant: str = ""):
    shape = SHAPES[shape_name]
    ok, why = applicable(arch, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name, "status": "SKIP", "reason": why}
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(mesh.devices.size)
    bundle = get_bundle(arch)
    cfg = bundle.cfg
    try:
        from repro.models.moe import expert_parallel_scope

        with expert_parallel_scope(mesh, cfg.expert_axis if cfg.moe else None):
            if shape.mode == "train":
                lowered = lower_train(bundle, mesh, shape, algorithm,
                                      consensus_impl=consensus_impl,
                                      exchange_dtype=exchange_dtype,
                                      codec=codec)
            elif shape.mode == "prefill":
                lowered = lower_prefill(bundle, mesh, shape)
            else:
                lowered = lower_decode(bundle, mesh, shape)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        cost = compiled.cost_analysis() or {}
        try:
            mem = compiled.memory_analysis()
            per_dev_mem = getattr(mem, "temp_size_in_bytes", None)
            if per_dev_mem is not None:
                per_dev_mem += getattr(mem, "argument_size_in_bytes", 0) + getattr(
                    mem, "output_size_in_bytes", 0
                )
        except Exception:
            per_dev_mem = None
        hlo = compiled.as_text()
        # trip-count-aware analysis (XLA cost_analysis counts while bodies
        # once — see launch/hlo_cost.py; raw values recorded for comparison)
        from repro.launch.hlo_cost import analyze

        hc = analyze(hlo)
        report = RooflineReport(
            arch=arch,
            shape=shape_name,
            mesh=mesh_name,
            chips=chips,
            hlo_flops=float(hc["flops"]),
            hlo_bytes=float(hc["bytes"]),
            collective_bytes=float(hc["collective_bytes"]),
            collective_breakdown=hc["collective_breakdown"],
            model_flops=model_flops(cfg, shape),
            per_device_memory_bytes=per_dev_mem,
        )
        row = report.row()
        row.update(
            variant=variant,
            status="OK",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            xla_cost_flops=float(cost.get("flops", 0.0)),
            xla_cost_bytes=float(cost.get("bytes accessed", 0.0)),
            hlo_warnings=hc["warnings"],
        )
        return row
    except Exception as e:
        return {
            "arch": arch,
            "shape": shape_name,
            "mesh": mesh_name,
            "status": "FAIL",
            "error": f"{type(e).__name__}: {e}",
            "trace": traceback.format_exc()[-2000:],
        }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--algorithm", default="drt", choices=["drt", "classical"])
    ap.add_argument("--consensus", default="gather", choices=["gather", "permute"])
    ap.add_argument("--exchange-dtype", default=None, choices=[None, "bfloat16"])
    ap.add_argument("--codec", default=None,
                    help="wire codec: identity|bf16|f16|int8|topk[:frac]")
    ap.add_argument("--out", default=None)
    ap.add_argument(
        "--graph-stats", action="store_true",
        help="print realized |E| / degree stats for --topology/--schedule "
             "over --agents (incl. the dense-vs-edge FLOP ratio the sparse "
             "consensus path exploits and the dense-vs-edge BYTE ratio of "
             "the wire-resident round under --codec's wire width) and exit",
    )
    ap.add_argument("--topology", default="ring",
                    help="graph for --graph-stats (e.g. ring, erdos_renyi)")
    ap.add_argument("--agents", type=int, default=16)
    ap.add_argument("--er-p", type=float, default=0.1,
                    help="erdos_renyi edge probability (paper uses 0.1)")
    ap.add_argument("--schedule", default=None,
                    help="schedule spec for --graph-stats (same grammar as "
                         "launch.train: name, 'periodic:a,b[@n]', 'gossip[:p]', "
                         "'onepeer')")
    ap.add_argument("--agent-dropout", type=float, default=0.0)
    ap.add_argument("--edge-dropout", type=float, default=0.0)
    ap.add_argument("--schedule-seed", type=int, default=0)
    ap.add_argument("--stats-rounds", type=int, default=None,
                    help="rounds to sample for --graph-stats (default: one "
                         "full schedule period)")
    args = ap.parse_args(argv)

    if args.graph_stats:
        from repro.core.dynamic import make_schedule, schedule_graph_stats

        tkw = (
            {"p": args.er_p, "seed": args.schedule_seed}
            if args.topology == "erdos_renyi" else {}
        )
        topo = make_topology(args.topology, args.agents, **tkw)
        sched = make_schedule(
            args.schedule if args.schedule is not None else topo,
            args.agents,
            agent_drop=args.agent_dropout,
            edge_drop=args.edge_dropout,
            seed=args.schedule_seed,
        )
        # wire width of --codec (bytes/element) for the byte-ratio column;
        # int8 (the gated codec) when no codec is named
        wire_w = {"bf16": 2, "f16": 2, "identity": 4, "topk": 4}.get(
            (args.codec or "int8").split(":")[0], 1
        )
        stats = {"topology": args.topology, "schedule": args.schedule,
                 **schedule_graph_stats(
                     sched, rounds=args.stats_rounds, wire_itemsize=wire_w
                 )}
        print(json.dumps(stats, indent=1, default=float))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(stats, f, indent=1, default=float)
        raise SystemExit(0)

    jobs = []
    archs = ASSIGNED_ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for m in meshes:
                jobs.append((a, s, m))

    results = []
    xd = jnp.bfloat16 if args.exchange_dtype == "bfloat16" else None
    variant = f"{args.algorithm}/{args.consensus}" + ("/bf16x" if xd is not None else "")
    if args.codec:
        variant += f"/{args.codec}"
    for a, s, m in jobs:
        row = run_one(a, s, m, args.algorithm, consensus_impl=args.consensus,
                      exchange_dtype=xd, codec=args.codec, variant=variant)
        results.append(row)
        status = row["status"]
        extra = (
            f"bottleneck={row.get('bottleneck')} compile={row.get('compile_s')}s"
            if status == "OK"
            else row.get("reason", row.get("error", ""))
        )
        print(f"[{status}] {a} x {s} x {row['mesh']}: {extra}", flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1, default=str)
        print(f"wrote {args.out}")
    n_fail = sum(r["status"] == "FAIL" for r in results)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
