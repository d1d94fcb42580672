"""Combine-step microbenchmark: the communication/compute cost of one
consensus ROUND-SET (the paper's 3 combination rounds), classical vs DRT,
per-leaf tree path vs the flat-slab hot path, per wire codec.

Measures wall-time of the local compute pieces on CPU and reports the
ANALYTIC per-agent collective volume (bytes received) for both exchange
engines across topologies and codecs — the quantities the §Perf hillclimb
drives down (ring: 2x params via ppermute vs 15x via all-gather at K=16;
int8/topk shave another >= 4x off either engine; the slab path removes the
per-leaf launch overhead: >= 2x us/call on the 10-group model at K=16).

PR 4 adds the *orchestration* metrics around the rounds:

  trace_compile      trace/compile wall-time of one jitted round-set at
                     rounds=8, scanned (lax.scan, O(1) in rounds) vs the
                     unrolled parity oracle (O(rounds)).
  dispatch           static Pallas-launch counts per round-set with
                     use_kernels=True (whole-slab batched kernels: ONE
                     launch per coded round, one per exact round-set).
  train_many_steps   steps/s of the donated multi-step driver
                     (``make_many_steps`` scanning local-step + consensus)
                     vs per-step jitted dispatch at 8 steps/call.

PR 6 adds ``telemetry``: us/call of the exact DRT slab round-set with
in-graph consensus telemetry (``obs=ObsConfig()``) vs disabled — the
near-free-when-enabled half of the observability contract (the
zero-cost-when-disabled half is a jaxpr-identity test).

PR 7 adds ``sparse``: the edge-list consensus path (``path="edge"``) vs
the dense coded round at K=16/64/256 on a ring — wall medians
(interleaved, compiled executables) AND XLA cost-analysis FLOPs/bytes per
program.  ``sparse_flop_speedup`` (dense/edge FLOPs) is the
machine-independent floor break and is hard-gated >= 1.5 at K=64 by
``check_regression.py``; ``sparse_speedup`` (wall) is tracked relatively
only, because on this bandwidth-bound single-core host the dense K²D
BLAS is compute-cheap while the edge path streams more bytes.  ``--K n
--path edge [--devices m]`` refreshes just the sparse section (the CI
large-K smoke runs it sharded over forced host devices).

PR 9 doubles the sparse rows (bf16 + int8 per K) and adds the
``repro.kernels.traffic`` columns pricing the FUSED rounds' HBM bytes —
``sparse_byte_ratio`` (wire-resident edge / dense fused, int8 hard-gated
< 1.0 at K=64) is the byte analogue of the FLOP gate: machine-independent,
derived from the Pallas grid structure itself.

Permute-engine rows carry the engine-specific wire volume only by default;
timing one needs a multi-device mesh, so those rows are tagged
``"untimed": true`` (instead of a null ``us_per_call``) and excluded from
every regression-gate computation.  ``--permute-timing`` opts into real
numbers: the process re-seeds ``XLA_FLAGS`` with 16 forced host devices
(the ``launch/mesh.py`` dry-run trick — must happen before jax imports,
hence the hook at the very top of this file) and times ``PermuteConsensus``
round-sets under ``shard_map``, replacing the ``untimed`` tags.  Those
numbers measure 16 oversubscribed host shards on one CPU — comparable
run-to-run, not against the single-process gather rows.

``codec_overhead`` tracks THE tentpole metric of the coded hot path: per
codec, slab-gather ``us_per_call / identity us_per_call`` — what a codec
costs in compute relative to the exact exchange (bytes saved are the
``recv_mb`` columns).  ``check_regression.py`` hard-gates int8.

Writes the perf-trajectory artifact ``BENCH_consensus.json`` at the repo
root (schema: {"K", "model", "rows": [...], "speedup_slab_vs_tree",
"codec_overhead", "trace_compile", "dispatch", "train_many_steps"}) so
future PRs can track regressions (benchmarks/check_regression.py gates on
it in CI).

Run:  PYTHONPATH=src python benchmarks/combine_micro.py [--permute-timing]
"""
from __future__ import annotations

import os
import sys

if "--permute-timing" in sys.argv:  # must precede any jax import
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=16 "
        + os.environ.get("XLA_FLAGS", "")
    )
if "--devices" in sys.argv:  # ditto: forced host devices for the sharded
    _n = sys.argv[sys.argv.index("--devices") + 1]  # large-K edge-path smoke
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_n} "
        + os.environ.get("XLA_FLAGS", "")
    )

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.comm import collective_bytes_per_step as codec_bytes_per_step
from repro.core import (
    DRTConfig,
    build_slab_layout,
    edge_stacks_from_topology,
    gather_consensus_rounds,
    make_topology,
)
from repro.utils.pytree import LayerPartition
from repro.utils import tree_bytes

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_consensus.json")
ROUNDS = 3  # the paper's consensus cadence; the slab packs ONCE per round-set
SCAN_ROUNDS = 8  # "heavy traffic" round count for the trace/compile contrast


def _atomic_json_dump(doc: dict, path: str) -> None:
    """Crash-safe bench-doc write: mkdir -p, dump to a same-directory temp
    file, fsync, then ``os.replace`` — a benchmark run killed mid-write can
    never leave CI a truncated JSON artifact."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = os.path.join(d, f".{os.path.basename(path)}.tmp")
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def _model_stack(key, K: int, n_layers: int = 8, width: int = 64):
    """10-group benchmark model: one stacked scan-over-layers group with six
    leaves per slot plus nine plain multi-leaf groups — a leaf-heavy shape
    (26 leaves, 10 groups) representative of scan-over-layers transformers,
    where the tree path pays per-leaf stats/combine passes every round."""

    def one(k):
        ks = jax.random.split(k, 16)
        w = width
        tree = {
            "embed": {"w": jax.random.normal(ks[0], (w, w)),
                      "b": jax.random.normal(ks[1], (w,))},
            "blocks": {
                "wq": jax.random.normal(ks[2], (n_layers, w, w)),
                "wk": jax.random.normal(ks[3], (n_layers, w, w)),
                "wv": jax.random.normal(ks[4], (n_layers, w, w)),
                "wo": jax.random.normal(ks[5], (n_layers, w, w)),
                "w1": jax.random.normal(ks[6], (n_layers, w, 2 * w)),
                "w2": jax.random.normal(ks[7], (n_layers, 2 * w, w)),
            },
            "head": {"w": jax.random.normal(ks[8], (w, w)),
                     "b": jax.random.normal(ks[9], (w,))},
        }
        for i in range(7):
            tree[f"norm{i}"] = {
                "scale": jax.random.normal(ks[10 + (i % 6)], (w,)),
                "bias": jax.random.normal(ks[10 + ((i + 1) % 6)], (w,)),
            }
        return tree

    return jax.vmap(one)(jax.random.split(key, K))


def _time(fn, *args, iters=9):
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]  # median: robust to noisy-neighbour containers


def _time_paired(fns: dict, *args, iters=15):
    """Interleaved median timing of several compiled callables — measuring
    A/B/A/B cancels slow machine-load drift out of the A-vs-B ratio."""
    ts = {k: [] for k in fns}
    for k, fn in fns.items():
        jax.block_until_ready(fn(*args))
    for _ in range(iters):
        for k, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts[k].append(time.perf_counter() - t0)
    out = {}
    for k, v in ts.items():
        v.sort()
        out[k] = v[len(v) // 2]
    return out


def run(K: int = 16, codecs=("identity", "bf16", "int8", "topk:0.1")):
    """Legacy row contract for benchmarks/run.py (one row per topology x
    algorithm) with the new tree-vs-slab round-set timings attached."""
    pK = _model_stack(jax.random.key(0), K)
    template = jax.tree.map(lambda x: x[0], pK)
    part = LayerPartition.build(template)
    layout = build_slab_layout(part, template)
    param_bytes = tree_bytes(template)
    rows = []
    for topo_name in ("ring", "hypercube", "full"):
        topo = make_topology(topo_name, K)
        C = jnp.asarray(topo.c_matrix(), jnp.float32)
        metro = jnp.asarray(topo.metropolis(), jnp.float32)
        for algo in ("classical", "drt"):
            fns = {
                path: jax.jit(
                    lambda pK, algo=algo, path=path: gather_consensus_rounds(
                        part, pK, C, DRTConfig(), rounds=ROUNDS, algorithm=algo,
                        metropolis=metro, path=path,
                        layout=layout if path == "slab" else None,
                    )[0]
                )
                for path in ("tree", "slab")
            }
            times = _time_paired(fns, pK)
            row = dict(
                topology=topo_name,
                algorithm=algo,
                us_per_call=times["slab"] * 1e6,  # the production (slab) path
                us_tree=times["tree"] * 1e6,
                us_slab=times["slab"] * 1e6,
                slab_speedup=times["tree"] / times["slab"],
                rounds=ROUNDS,
                param_mb=param_bytes / 1e6,
            )
            for codec in codecs:
                gather = codec_bytes_per_step(topo, template, "gather", codec=codec)
                perm = codec_bytes_per_step(topo, template, "permute", codec=codec)
                tag = codec.replace(":", "")
                row[f"gather_recv_mb_{tag}"] = gather["recv_bytes"] / 1e6
                row[f"permute_recv_mb_{tag}"] = perm["recv_bytes"] / 1e6
            # legacy column names (benchmarks/run.py) = the f32 identity wire
            row["gather_recv_mb"] = row["gather_recv_mb_identity"]
            row["permute_recv_mb"] = row["permute_recv_mb_identity"]
            row["saving"] = (
                row["gather_recv_mb_identity"] / max(row["permute_recv_mb_identity"], 1e-9)
            )
            rows.append(row)
    return rows


def run_codec_paths(
    K: int = 16,
    codecs=("identity", "bf16", "int8", "topk:0.1"),
    permute_times: "dict | None" = None,
):
    """Per-codec tree-vs-slab round-set timings on the ring (gather engine):
    the BENCH_consensus.json trajectory rows.  ``permute_times`` (from
    :func:`run_permute_timing`) fills the permute rows' ``us_per_call``
    instead of tagging them ``untimed``."""
    pK = _model_stack(jax.random.key(0), K)
    template = jax.tree.map(lambda x: x[0], pK)
    part = LayerPartition.build(template)
    layout = build_slab_layout(part, template)
    topo = make_topology("ring", K)
    C = jnp.asarray(topo.c_matrix(), jnp.float32)
    metro = jnp.asarray(topo.metropolis(), jnp.float32)
    rng = jax.random.key(1)
    # ONE interleaved timing group across every (codec, path): the
    # codec_overhead_ratio compares codecs AGAINST EACH OTHER, so they must
    # share the same machine-load window — per-codec groups measured minutes
    # apart put machine drift, not codec cost, into the ratio
    fns = {
        (codec, path): jax.jit(
            lambda pK, codec=codec, path=path: gather_consensus_rounds(
                part, pK, C, DRTConfig(), rounds=ROUNDS, algorithm="drt",
                metropolis=metro, codec=codec, rng=rng, path=path,
                layout=layout if path == "slab" else None,
            )[0]
        )
        for codec in codecs
        for path in ("tree", "slab")
    }
    times = _time_paired(fns, pK, iters=9)
    rows = []
    for codec in codecs:
        for path in ("tree", "slab"):
            for engine in ("gather", "permute"):
                vol = codec_bytes_per_step(topo, template, engine, codec=codec)
                row = dict(
                    engine=engine,
                    path=path,
                    codec=codec,
                    topology="ring",
                    algorithm="drt",
                    rounds=ROUNDS,
                    recv_mb_per_round=vol["recv_bytes"] / 1e6,
                )
                if engine == "gather":
                    row["us_per_call"] = times[(codec, path)] * 1e6
                elif permute_times and (codec, path) in permute_times:
                    row["us_per_call"] = permute_times[(codec, path)] * 1e6
                    row["timing"] = "shard_map/16 forced host devices"
                else:
                    # timings are measured on the GATHER round-set only; a
                    # permute timing needs a multi-device mesh (opt in with
                    # --permute-timing).  Tag the row instead of emitting a
                    # null us_per_call so downstream math can't trip on it.
                    row["untimed"] = True
                rows.append(row)
    return rows


def run_permute_timing(K: int = 16, codecs=("identity", "bf16", "int8", "topk:0.1")):
    """Wall-time PermuteConsensus round-sets under ``shard_map`` on forced
    host devices (``--permute-timing`` re-execs jax with
    ``--xla_force_host_platform_device_count=16``, the ``launch/mesh.py``
    dry-run trick).  Returns ``{(codec, path): seconds_per_call}``.

    16 shards oversubscribe one CPU, so these numbers are comparable
    run-to-run (and against each other) but NOT against the single-process
    gather rows."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core.consensus import PermuteConsensus

    if jax.device_count() < K:
        raise RuntimeError(
            f"--permute-timing needs {K} devices; run via "
            "`python benchmarks/combine_micro.py --permute-timing` (the flag "
            "must be on the command line before jax initializes)"
        )
    mesh = jax.make_mesh((K,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    pK = _model_stack(jax.random.key(0), K)
    part = LayerPartition.build(jax.tree.map(lambda x: x[0], pK))
    topo = make_topology("ring", K)
    rng = jax.random.key(1)
    specs = jax.tree.map(lambda _: P("data"), pK)
    fns = {}
    for codec in codecs:
        for path in ("tree", "slab"):
            eng = PermuteConsensus(
                part, topo, DRTConfig(), axis_name="data", codec=codec,
                path=path,
            )

            def body(local, eng=eng):
                sq = jax.tree.map(lambda x: x[0], local)
                out, _ = eng(sq, rng=rng, rounds=ROUNDS)
                return jax.tree.map(lambda x: x[None], out)

            fns[(codec, path)] = jax.jit(
                shard_map(
                    body, mesh=mesh, in_specs=(specs,), out_specs=specs,
                    check_vma=False,
                )
            )
    return _time_paired(fns, pK, iters=5)


def _kernel_traffic_columns(layout, K, e_max, dmax, codec) -> dict:
    """Machine-independent HBM bytes of ONE fused coded round, priced by the
    ``repro.kernels.traffic`` grid-walk model (XLA cost analysis cannot see
    inside a Pallas launch; the grid structure fully determines the bytes):
    the dense ``slab_encode_combine`` round vs the wire-resident
    ``slab_edge_encode_combine`` round vs the pre-PR-9 decoded-slab edge
    round.  ``sparse_byte_ratio`` (edge/dense) is the hard-gated headline —
    < 1.0 means a sparse round streams FEWER bytes than a dense one."""
    from repro.kernels import traffic

    mode = {
        "bf16": "bf16", "f16": "f16", "int8": "int8", None: "exact",
    }.get(codec if codec is None else codec.split(":")[0], "sent")
    nb = layout.D // layout.lane
    n_segs = int(layout.col_scale_seg.max()) + 1
    L = layout.num_layers
    dense = traffic.dense_round_traffic(
        K, nb, mode if mode != "exact" else "bf16", L, n_segs=n_segs,
        lane=layout.lane,
    )["total"]
    edge = traffic.edge_round_traffic(
        K, nb, e_max, dmax, mode, L, n_segs=n_segs, lane=layout.lane
    )["total"]
    old = traffic.decoded_edge_round_traffic(
        K, nb, e_max, mode, L, lane=layout.lane
    )["total"]
    return dict(
        kernel_bytes_dense=dense,
        kernel_bytes_edge=edge,
        kernel_bytes_edge_decoded=old,
        sparse_byte_ratio=edge / dense,
    )


def run_sparse_paths(
    Ks=(16, 64, 256), rounds: int = ROUNDS, time_dense: bool = True,
    dense_timed_max: int = 256, wall_timed_max: int = 64,
    codecs=("bf16", "int8"),
):
    """Dense O(K^2 D) vs sparse edge-list O(|E| D) CODED round-sets on the
    ring — the agent-axis scaling trajectory (``sparse_speedup`` rows, gated
    by check_regression.py).  The coded path is where the dense floor lives:
    every dense coded round pays the (L, K, K)-vs-slab Gram stats plus the
    (K, K) combine contraction, both O(K^2 D), while the edge round streams
    O(|E| D) + O(Dmax K D).  Each timed row records BOTH wall time and
    XLA's own cost analysis: ``sparse_flop_speedup`` (dense FLOPs / edge
    FLOPs — the machine-independent O(K^2 D) -> O(|E| D) floor break,
    hard-gated >= 1.5 at K=64 by check_regression.py) and bytes accessed.
    Wall ``sparse_speedup`` is tracked relatively (no silent regression):
    on this bandwidth-bound single-core host (~5 GB/s streaming vs ~43
    GF/s BLAS) the dense contractions are compute-cheap enough that wall
    stays near parity at every K even as the FLOP gap reaches 29x — the
    wall win needs hardware whose matmul:bandwidth ratio is less lopsided
    or a fused segment kernel (see kernels/slab_segment.py, interpret-mode
    on CPU).

    PR 9 adds one row per (K, codec) — bf16 (the legacy trajectory rows)
    and int8 — plus the ``repro.kernels.traffic`` byte columns pricing the
    FUSED kernels (``kernel_bytes_dense`` / ``kernel_bytes_edge`` /
    ``kernel_bytes_edge_decoded`` and ``sparse_byte_ratio`` = edge/dense).
    The XLA ``bytes_*`` columns price the portable jnp programs these tests
    pin; the kernel columns price the wire-resident Pallas round, whose
    int8 ``sparse_byte_ratio`` is hard-gated < 1.0 at K=64 by
    check_regression.py — the byte analogue of the FLOP floor break.

    ``K > dense_timed_max`` (or ``time_dense=False``, the ``--path edge``
    CI smoke) skips the dense timing — those rows carry the analytic FLOP
    ratio and an ``untimed`` dense tag instead.  ``K > wall_timed_max``
    still compiles the dense program for its (stable, machine-independent)
    XLA cost analysis but skips the dense WALL pairing: the K=256 slab is
    ~280 MB and its wall ratio swings 4x run-to-run on the CI container
    (page-cache state dominates), so gating it relatively is pure noise —
    those rows carry ``dense_wall_untimed`` and check_regression tracks
    only their FLOP/byte columns.  Under a forced
    multi-device host (``--devices N``) the slab's agent axis and the edge
    tables are placed with the ``launch/sharding.py`` consensus specs,
    exercising the sharded large-K path end-to-end."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import max_in_degree_from_topology

    n_dev = jax.device_count()
    rng = jax.random.key(11)
    rows = []
    for K in Ks:
        pK = _model_stack(jax.random.key(0), K)
        template = jax.tree.map(lambda x: x[0], pK)
        part = LayerPartition.build(template)
        layout = build_slab_layout(part, template)
        topo = make_topology("ring", K)
        C = jnp.asarray(topo.c_matrix(), jnp.float32)
        metro = jnp.asarray(topo.metropolis(), jnp.float32)
        edges = edge_stacks_from_topology(topo, rounds)
        dmax = max_in_degree_from_topology(topo)
        e_dir = int(jnp.sum(edges.w[0] > 0.0))
        sharded = n_dev > 1 and K % n_dev == 0
        if sharded:
            from repro.launch.sharding import edge_stack_pspecs

            mesh = jax.make_mesh((n_dev,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
            pK = jax.tree.map(
                lambda x: jax.device_put(x, NamedSharding(mesh, P("data"))), pK
            )
            edges = type(edges)(
                *(
                    jax.device_put(x, NamedSharding(mesh, s))
                    for x, s in zip(edges, edge_stack_pspecs(mesh, e_dir))
                )
            )
        for codec in codecs:
            common = dict(
                rounds=rounds, algorithm="drt", metropolis=metro,
                layout=layout, codec=codec,
                rng=rng if codec is not None else None,
            )
            fns = {
                "dense": jax.jit(
                    lambda pK, common=common: gather_consensus_rounds(
                        part, pK, C, DRTConfig(), path="slab", **common
                    )[0]
                ),
                "edge": jax.jit(
                    lambda pK, common=common: gather_consensus_rounds(
                        part, pK, C, DRTConfig(), path="edge", edges=edges,
                        max_in_degree=dmax, **common
                    )[0]
                ),
            }
            row = dict(
                K=K,
                topology="ring",
                algorithm="drt",
                codec=codec or "none",
                rounds=rounds,
                directed_edges=e_dir,
                max_in_degree=dmax,
                dense_vs_edge_flop_ratio=K * K / e_dir,
                devices=n_dev,
                sharded=sharded,
            )
            row.update(_kernel_traffic_columns(
                layout, K, int(edges.src.shape[-1]), dmax, codec
            ))
            iters = 9 if K <= 16 else (5 if K <= 64 else 3)
            if time_dense and K <= dense_timed_max:
                compiled = {k: f.lower(pK).compile() for k, f in fns.items()}
                cost = {k: ex.cost_analysis() for k, ex in compiled.items()}
                row.update(
                    flops_dense=cost["dense"].get("flops", 0.0),
                    flops_edge=cost["edge"].get("flops", 0.0),
                    bytes_dense=cost["dense"].get("bytes accessed", 0.0),
                    bytes_edge=cost["edge"].get("bytes accessed", 0.0),
                    sparse_flop_speedup=(
                        cost["dense"].get("flops", 0.0)
                        / max(cost["edge"].get("flops", 0.0), 1.0)
                    ),
                )
                if K <= wall_timed_max:
                    times = _time_paired(compiled, pK, iters=iters)
                    row.update(
                        us_dense=times["dense"] * 1e6,
                        us_edge=times["edge"] * 1e6,
                        sparse_speedup=times["dense"] / times["edge"],
                    )
                else:
                    row.update(
                        us_edge=_time(fns["edge"], pK, iters=iters) * 1e6,
                        dense_wall_untimed=True,
                    )
            else:
                row.update(us_edge=_time(fns["edge"], pK, iters=iters) * 1e6,
                           dense_untimed=True)
            rows.append(row)
    return rows


def update_sparse_section(path: str, Ks, time_dense: bool = True) -> dict:
    """Re-measure the sparse rows for ``Ks`` and merge them into the bench
    doc at ``path`` (rows for other K values are kept) — the large-K CI
    smoke refreshes K=64 without re-running the full suite."""
    rows = run_sparse_paths(Ks=Ks, time_dense=time_dense)
    try:
        with open(path) as f:
            doc = json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        doc = {"generated_by": "benchmarks/combine_micro.py"}
    sec = doc.setdefault("sparse", {"rounds": ROUNDS})
    new_keys = {(r2["K"], r2["codec"]) for r2 in rows}
    keep = [
        r for r in sec.get("rows", [])
        if (r["K"], r.get("codec", "none")) not in new_keys
    ]
    sec["rows"] = sorted(keep + rows, key=lambda r: (r["K"], r["codec"]))
    _atomic_json_dump(doc, path)
    return doc


def run_trace_compile(K: int = 16, rounds: int = SCAN_ROUNDS, codecs=(None, "bf16")):
    """Trace/compile wall-time of ONE jitted round-set: scanned (lax.scan,
    O(1) in rounds) vs the unrolled parity oracle (O(rounds)) — the metric
    that keeps the scanned hot path's sub-linear trace cost from silently
    regressing.  ``None`` exercises the exact Gram-recurrence path, ``bf16``
    the full coded slab round body (int8 shows an even starker gap — 3.6s
    scanned vs 104s unrolled, XLA constant-folds the unrolled uniforms — but
    is too expensive to pay on every CI run)."""
    pK = _model_stack(jax.random.key(0), K)
    template = jax.tree.map(lambda x: x[0], pK)
    part = LayerPartition.build(template)
    layout = build_slab_layout(part, template)
    topo = make_topology("ring", K)
    C = jnp.asarray(topo.c_matrix(), jnp.float32)
    metro = jnp.asarray(topo.metropolis(), jnp.float32)
    rng = jax.random.key(1)
    rows = []
    for codec in codecs:
        for variant, unroll in (("scanned", False), ("unrolled", True)):
            fn = jax.jit(
                lambda pK, codec=codec, unroll=unroll: gather_consensus_rounds(
                    part, pK, C, DRTConfig(), rounds=rounds, algorithm="drt",
                    metropolis=metro, codec=codec,
                    rng=rng if codec is not None else None,
                    layout=layout, unroll=unroll,
                )[0]
            )
            t0 = time.perf_counter()
            lowered = fn.lower(pK)
            trace_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            lowered.compile()
            compile_s = time.perf_counter() - t0
            rows.append(dict(
                codec=codec or "none",
                variant=variant,
                rounds=rounds,
                trace_ms=trace_s * 1e3,
                compile_ms=compile_s * 1e3,
            ))
    return rows


def run_telemetry_overhead(K: int = 16, rounds: int = ROUNDS):
    """Runtime cost of the in-graph telemetry (repro.obs): interleaved
    medians of the exact DRT slab round-set with ``obs=None`` (must trace to
    the pre-telemetry program — asserted in tests/test_obs.py) vs
    ``obs=ObsConfig()`` (per-round ConsensusMetrics ride the scan ys).  The
    enabled path reads disagreement/DRT distances off the carried Gram
    recurrence, so the ratio should stay ~1.0; check_regression.py hard-gates
    it below 1.05."""
    from repro.obs.metrics import ObsConfig

    pK = _model_stack(jax.random.key(0), K)
    template = jax.tree.map(lambda x: x[0], pK)
    part = LayerPartition.build(template)
    layout = build_slab_layout(part, template)
    topo = make_topology("ring", K)
    C = jnp.asarray(topo.c_matrix(), jnp.float32)
    metro = jnp.asarray(topo.metropolis(), jnp.float32)
    fns = {
        name: jax.jit(
            lambda pK, obs=obs: gather_consensus_rounds(
                part, pK, C, DRTConfig(), rounds=rounds, algorithm="drt",
                metropolis=metro, layout=layout, obs=obs,
            )[0]
        )
        for name, obs in (("disabled", None), ("enabled", ObsConfig()))
    }
    times = _time_paired(fns, pK, iters=15)
    return dict(
        rounds=rounds,
        us_disabled=times["disabled"] * 1e6,
        us_enabled=times["enabled"] * 1e6,
        overhead_ratio=times["enabled"] / times["disabled"],
    )


def run_consensus_control(
    K: int = 16, max_rounds: int = 16, sets: int = 4, betas=(0.0, 0.2, 0.4)
):
    """Consensus-control trajectory on the K=16 ring (exact DRT slab):

    ``momentum``: per heavy-ball beta, the disagreement after ``max_rounds``
    fixed rounds and the round count needed to reach the beta=0 fixed-budget
    disagreement — ``momentum_rounds_ratio`` (best beta's count over beta=0's)
    is hard-gated <= 1.0 by check_regression.py (momentum must never need
    MORE rounds than plain mixing to reach the same disagreement).

    ``max_rounds`` defaults to 16 — on the K=16 ring (mixing time ~K^2/pi^2
    ~ 26 rounds) heavy-ball needs a few rounds to build its velocity, so a
    too-short budget understates both metrics.

    ``adaptive``: ``sets`` successive round-sets with fresh per-agent noise
    regrown between them (the local-SGD divergence pattern a training loop
    produces).  Per set, a fixed ``max_rounds`` momentum-free run defines the
    target disagreement; the adaptive run (best beta, ``round_tol`` = that
    target) reaches it while the disagreement gate turns the tail rounds
    into in-graph no-ops.  ``round_savings = 1 - mean_effective/max_rounds``
    is hard-gated >= 0.25: the adaptive budget must save at least a quarter
    of the fixed budget at matched disagreement."""
    import numpy as np

    from repro.obs.metrics import ObsConfig

    pK = _model_stack(jax.random.key(0), K)
    template = jax.tree.map(lambda x: x[0], pK)
    part = LayerPartition.build(template)
    layout = build_slab_layout(part, template)
    topo = make_topology("ring", K)
    C = jnp.asarray(topo.c_matrix(), jnp.float32)
    metro = jnp.asarray(topo.metropolis(), jnp.float32)
    obs = ObsConfig()

    def round_set(p, beta, tol=None):
        return gather_consensus_rounds(
            part, p, C, DRTConfig(), rounds=max_rounds, algorithm="drt",
            metropolis=metro, layout=layout, momentum=beta, round_tol=tol,
            obs=obs,
        )

    # -- momentum: rounds-to-tolerance at the fixed budget ------------------
    _, _, _, base_cm = round_set(pK, 0.0)
    target = float(base_cm.disagreement[-1])
    best_beta = max(betas)
    mom_rows = []
    rounds_to = {}
    for beta in betas:
        _, _, _, cm = round_set(pK, beta)
        dis = np.asarray(cm.disagreement)
        hit = np.nonzero(dis <= target * (1 + 1e-6))[0]
        n = int(hit[0]) + 1 if hit.size else max_rounds
        rounds_to[beta] = n
        mom_rows.append(dict(
            beta=beta, rounds=max_rounds, final_disagreement=float(dis[-1]),
            rounds_to_fixed_target=n,
        ))
    momentum_rounds_ratio = rounds_to[best_beta] / rounds_to[0.0]

    # -- adaptive: effective rounds at matched disagreement -----------------
    adaptive_rows = []
    p = pK
    noise_keys = jax.random.split(jax.random.key(7), sets)
    for s in range(sets):
        out_f, _, _, cm_f = round_set(p, 0.0)
        tol_s = float(cm_f.disagreement[-1])
        _, _, _, cm_a = round_set(p, best_beta, tol=tol_s)
        eff = float(cm_a.effective_rounds[-1])
        adaptive_rows.append(dict(
            set=s, round_tol=tol_s, effective_rounds=eff,
            final_disagreement=float(cm_a.disagreement[-1]),
        ))
        # regrow per-agent divergence around the mixed point for the next set
        leaves, treedef = jax.tree.flatten(out_f)
        ks = jax.random.split(noise_keys[s], len(leaves))
        p = jax.tree.unflatten(treedef, [
            x + 0.5 * jax.random.normal(k, x.shape, x.dtype)
            for x, k in zip(leaves, ks)
        ])
    mean_eff = float(np.mean([r["effective_rounds"] for r in adaptive_rows]))
    return dict(
        K=K,
        max_rounds=max_rounds,
        topology="ring",
        algorithm="drt",
        momentum_rows=mom_rows,
        momentum_rounds_ratio=momentum_rounds_ratio,
        adaptive_beta=best_beta,
        adaptive_rows=adaptive_rows,
        mean_effective_rounds=mean_eff,
        round_savings=1.0 - mean_eff / max_rounds,
    )


def run_byzantine(
    K: int = 16,
    rounds: int = 8,
    fraction: float = 0.25,
    fault: str = "sign_flip",
    clip: float = 0.15,
):
    """Byzantine-robustness trajectory on the K=16 ring: floor(fraction * K)
    seeded agents publish through ``fault`` every round while honest agents
    try to reach consensus.

    The model is a compact TWO-layer stack, deliberately much shallower
    than the 26-leaf ``_model_stack`` used elsewhere in this file.  Eq. 14's
    numerator is a product over layers of ``(1 + d2_q / n2_q)``; an
    every-layer attack like a sign flip contributes ``~(1 + 4) = 5`` per
    layer, so with L layers the Byzantine/honest weight ratio scales as
    ``5**L * d_honest**2 / (4 n**2)``.  For small L the honest term wins and
    DRT down-weights the attacker; by L ~ 26 the product saturates the
    Lemma-1 clamp and the normalized weights go uniform — DRT's
    discriminative regime is few-layer (or per-layer-group) trust, which is
    what this benchmark measures.

    Agents start CLUSTERED (same base point + 5% per-agent spread — the
    ``same_init`` training regime where honest iterates are mutually close
    and a sign-flipped publication is a geometric outlier).  Each cell
    reports the final mean squared distance of the HONEST cohort to the
    INITIAL honest mean — the point attack-free consensus would reach, so
    the number penalizes both residual disagreement and attacker-induced
    drift — plus the mean per-round ``byzantine_weight_mass`` telemetry.

    Two hard gates ride this section (checked by check_regression.py):

    - ``gap_vs_metropolis`` = undefended-Metropolis honest drift over
      DRT+clip honest drift, gated > 1.0 — the paper's trust mechanism plus
      clipping must strictly beat weight-oblivious averaging under a 25%
      sign-flip attack;
    - ``byzantine_weight_mass`` (DRT+clip cell), gated < ``fraction`` — the
      trust mass Byzantine publications capture must sit measurably below
      the uniform-attention baseline.
    """
    import numpy as np

    from repro.faults import make_fault_plan
    from repro.obs.metrics import ObsConfig

    k0, k1, kn0, kn1 = jax.random.split(jax.random.key(0), 4)
    base = {
        "w": jax.random.normal(k0, (32, 32), jnp.float32),
        "b": jax.random.normal(k1, (128,), jnp.float32),
    }
    noise = {
        "w": jax.random.normal(kn0, (K, 32, 32), jnp.float32),
        "b": jax.random.normal(kn1, (K, 128), jnp.float32),
    }
    pK = jax.tree.map(lambda x, n: x[None] + 0.05 * n, base, noise)
    template = jax.tree.map(lambda x: x[0], pK)
    part = LayerPartition.build(template)
    layout = build_slab_layout(part, template)
    topo = make_topology("ring", K)
    C = jnp.asarray(topo.c_matrix(), jnp.float32)
    metro = jnp.asarray(topo.metropolis(), jnp.float32)
    plan = make_fault_plan(K, byzantine=fraction, fault_model=fault, seed=0)
    honest = ~plan.mask.mask_at(0)  # static membership (cycle=1)

    idx = np.nonzero(np.asarray(honest))[0]
    ref = jax.tree.map(
        lambda x: np.asarray(x, np.float64)[idx].mean(axis=0), pK
    )  # initial honest mean: the attack-free consensus target

    def honest_drift(out) -> float:
        tot = 0.0
        for leaf, r in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
            x = np.asarray(leaf, np.float64)[idx]
            tot += ((x - r[None]) ** 2).sum()
        return tot / len(idx)

    def cell(name: str, algorithm: str = "drt", **kw) -> dict:
        out, _, _, cm = gather_consensus_rounds(
            part, pK, C, DRTConfig(), rounds=rounds, algorithm=algorithm,
            metropolis=metro, layout=layout, faults=plan.realize(0, rounds),
            obs=ObsConfig(), **kw,
        )
        return dict(
            cell=name,
            algorithm=algorithm,
            disagreement_to_honest_mean=honest_drift(out),
            byzantine_weight_mass=float(
                np.mean(np.asarray(cm.byzantine_weight_mass))
            ),
            **{k: v for k, v in kw.items()},
        )

    rows = [
        cell("metropolis", algorithm="classical"),
        cell("drt"),
        cell("drt_clip", trust_clip=clip),
        cell("trimmed", combine="trimmed:0.25"),
        cell("median", combine="median"),
    ]
    by = {r["cell"]: r for r in rows}
    return dict(
        K=K,
        rounds=rounds,
        topology="ring",
        fraction=fraction,
        fault_model=fault,
        trust_clip=clip,
        n_byzantine=int(K * fraction),
        rows=rows,
        gap_vs_metropolis=(
            by["metropolis"]["disagreement_to_honest_mean"]
            / by["drt_clip"]["disagreement_to_honest_mean"]
        ),
        byzantine_weight_mass=by["drt_clip"]["byzantine_weight_mass"],
    )


def run_dispatch_counts(K: int = 16, rounds: int = ROUNDS):
    """Static Pallas-launch counts of one ``use_kernels=True`` round-set:
    the whole-slab batched kernels issue ONE launch per coded round (and one
    per round-SET on the exact Gram path), independent of the model's
    (groups x slots) layer count."""
    from repro.utils.dispatch import count_pallas_launches

    pK = _model_stack(jax.random.key(0), K)
    template = jax.tree.map(lambda x: x[0], pK)
    part = LayerPartition.build(template)
    layout = build_slab_layout(part, template)
    topo = make_topology("ring", K)
    C = jnp.asarray(topo.c_matrix(), jnp.float32)
    rng = jax.random.key(1)
    rows = []
    for codec in (None, "bf16", "int8"):
        n = count_pallas_launches(
            lambda pK, codec=codec: gather_consensus_rounds(
                part, pK, C, DRTConfig(), rounds=rounds, algorithm="drt",
                codec=codec, rng=rng if codec is not None else None,
                layout=layout, use_kernels=True,
            )[0],
            pK,
        )
        rows.append(dict(
            codec=codec or "none",
            rounds=rounds,
            pallas_launches=n,
            launches_per_round=n / rounds,
        ))
    return rows


def run_train_chunking(
    K: int = 4,
    steps_per_call: int = 8,
    width: int = 16,
    n_layers: int = 2,
    iters: int = 15,
):
    """Dispatch amortization of the donated multi-step driver: steps/s of
    the per-step jitted (local-step + consensus) loop vs ONE
    ``make_many_steps`` program scanning ``steps_per_call`` steps, on a
    reduced-width variant of the benchmark model (small enough that per-step
    host dispatch is a visible fraction of the step — exactly the regime the
    driver exists for)."""
    from repro.core import DecentralizedTrainer, TrainerConfig, make_topology as mk
    from repro.optim import sgd

    def init_fn(key):
        return jax.tree.map(
            lambda x: x[0], _model_stack(key, 1, n_layers=n_layers, width=width)
        )

    def loss_fn(params, batch, rng):
        reg = sum(jnp.sum(jnp.square(l)) for l in jax.tree.leaves(params))
        return jnp.sum((params["embed"]["b"] - batch) ** 2) + 1e-3 * reg

    tr = DecentralizedTrainer(
        loss_fn, init_fn, sgd(0.05), mk("ring", K),
        TrainerConfig(algorithm="drt", consensus_steps=ROUNDS),
    )
    state0 = tr.init(jax.random.key(0))
    targets = jax.random.normal(jax.random.key(1), (K, width))
    batches = jnp.broadcast_to(targets, (steps_per_call, K, width))
    keys = jnp.stack([jax.random.key(i) for i in range(steps_per_call)])

    single = jax.jit(
        lambda st, b, k: tr.consensus(tr.local_step(st, b, k)[0])[0]
    )
    many = tr.make_many_steps()  # jitted + donated

    def run_single(st):
        for i in range(steps_per_call):
            st = single(st, targets, keys[i])
        return st

    def run_many(st):
        st, _ = many(st, batches, keys)
        return st

    # warm up both programs (many donates: feed it a fresh copy each call)
    jax.block_until_ready(run_single(state0))
    st_m = jax.tree.map(jnp.copy, state0)
    st_m = run_many(st_m)
    jax.block_until_ready(st_m)
    t_single, t_many = [], []
    st_s = state0
    for _ in range(iters):
        t0 = time.perf_counter()
        st_s = run_single(st_s)
        jax.block_until_ready(st_s)
        t_single.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        st_m = run_many(st_m)
        jax.block_until_ready(st_m)
        t_many.append(time.perf_counter() - t0)
    t_single.sort()
    t_many.sort()
    med_s = t_single[len(t_single) // 2]
    med_m = t_many[len(t_many) // 2]
    return dict(
        steps_per_call=steps_per_call,
        K=K,
        model=f"bench stack width={width} n_layers={n_layers}",
        consensus_rounds=ROUNDS,
        us_per_step_single=med_s / steps_per_call * 1e6,
        us_per_step_chunked=med_m / steps_per_call * 1e6,
        steps_per_s_single=steps_per_call / med_s,
        steps_per_s_chunked=steps_per_call / med_m,
        speedup_many_steps=med_s / med_m,
    )


def codec_overhead_ratios(rows) -> dict:
    """Per-codec ``codec_overhead_ratio``: slab-gather coded us_per_call over
    the identity (exact) slab-gather us_per_call — the compute price of a
    codec's bytes-on-wire savings.  Interleaved same-machine medians, so the
    ratio is robust to absolute runner speed.  Untimed rows never enter."""
    by = {
        (r["codec"], r["path"]): r["us_per_call"]
        for r in rows
        if r["engine"] == "gather" and not r.get("untimed")
    }
    base = by.get(("identity", "slab"))
    if not base:
        return {}
    return {
        codec: us / base
        for (codec, path), us in sorted(by.items())
        if path == "slab" and codec != "identity"
    }


def write_bench_json(
    path: str = BENCH_JSON, K: int = 16, permute_timing: bool = False,
    sparse_Ks=(16, 64, 256),
) -> dict:
    """Emit the perf-trajectory artifact consumed by CI and future PRs."""
    permute_times = run_permute_timing(K=K) if permute_timing else None
    rows = run_codec_paths(K=K, permute_times=permute_times)
    by = {(r["codec"], r["path"]): r for r in rows if r["engine"] == "gather"}
    speedup = by[("identity", "tree")]["us_per_call"] / by[("identity", "slab")]["us_per_call"]
    doc = {
        "generated_by": "benchmarks/combine_micro.py",
        "K": K,
        "model": "10-group / 26-leaf benchmark stack (see _model_stack)",
        "rounds_per_call": ROUNDS,
        "speedup_slab_vs_tree": speedup,
        "codec_overhead": codec_overhead_ratios(rows),
        "rows": rows,
        "sparse": {"rounds": ROUNDS, "rows": run_sparse_paths(Ks=sparse_Ks)},
        "trace_compile": {"rounds": SCAN_ROUNDS, "rows": run_trace_compile(K=K)},
        "dispatch": {"rounds": ROUNDS, "rows": run_dispatch_counts(K=K)},
        "train_many_steps": run_train_chunking(),
        "telemetry": run_telemetry_overhead(K=K),
        "control": run_consensus_control(K=K),
        "byzantine": run_byzantine(K=K),
    }
    _atomic_json_dump(doc, path)
    return doc


def _print_sparse(doc):
    print(f"\nsparse edge path vs dense O(K^2 D) (coded drt round-sets, "
          f"ring, {doc['sparse']['rounds']} rounds/call):")
    print(f"{'K':>4s} {'codec':>6s} {'|E|dir':>7s} {'us dense':>10s} "
          f"{'us edge':>10s} {'wall':>7s} {'flops':>7s} "
          f"{'kernel bytes':>12s} {'flop K^2/|E|':>13s} {'devices':>8s}")
    for r in doc["sparse"]["rows"]:
        dense = ("untimed" if "us_dense" not in r
                 else f"{r['us_dense']:.0f}")
        sp = ("-" if "sparse_speedup" not in r
              else f"{r['sparse_speedup']:.2f}x")
        fsp = (
            "-" if "sparse_flop_speedup" not in r
            else f"{r['sparse_flop_speedup']:.1f}x"
        )
        byr = (
            f"{r['sparse_byte_ratio']:.3f}"
            if "sparse_byte_ratio" in r else "-"
        )
        print(f"{r['K']:4d} {r.get('codec', 'none'):>6s} "
              f"{r['directed_edges']:7d} {dense:>10s} "
              f"{r['us_edge']:10.0f} {sp:>7s} {fsp:>7s} {byr:>12s} "
              f"{r['dense_vs_edge_flop_ratio']:13.1f} {r['devices']:8d}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--permute-timing", action="store_true",
                    help="time PermuteConsensus on 16 forced host devices")
    ap.add_argument("--K", default=None,
                    help="comma list of agent counts for the sparse "
                         "dense-vs-edge sweep (default 16,64,256 — K=256 is "
                         "the gated crossover row)")
    ap.add_argument("--path", default="all", choices=["all", "edge"],
                    help="'edge' re-measures ONLY the sparse edge rows and "
                         "merges them into the existing bench doc (the "
                         "large-K CI smoke); 'all' runs the full suite")
    ap.add_argument("--devices", type=int, default=None,
                    help="force N host devices (must be on the command line "
                         "— consumed before jax init) so the sparse sweep "
                         "runs with the agent axis sharded over a data mesh")
    ap.add_argument("--out", default=BENCH_JSON,
                    help="bench doc path (default: repo-root BENCH_consensus.json)")
    args = ap.parse_args(argv)
    sparse_Ks = (
        tuple(int(k) for k in args.K.split(",")) if args.K else (16, 64, 256)
    )

    if args.path == "edge":
        doc = update_sparse_section(args.out, sparse_Ks, time_dense=False)
        _print_sparse(doc)
        print(f"\nupdated sparse rows in {os.path.abspath(args.out)}")
        return doc["sparse"]["rows"]

    doc = write_bench_json(
        args.out, permute_timing=args.permute_timing, sparse_Ks=sparse_Ks
    )
    print(f"slab vs tree (identity, gather, K={doc['K']}, "
          f"{doc['rounds_per_call']} rounds/call): {doc['speedup_slab_vs_tree']:.2f}x")
    print(f"{'engine':8s} {'path':5s} {'codec':10s} {'us/call':>10s} {'recv MB/round':>14s}")
    for r in doc["rows"]:
        us = "untimed" if r.get("untimed") else f"{r['us_per_call']:.0f}"
        print(f"{r['engine']:8s} {r['path']:5s} {r['codec']:10s} "
              f"{us:>10s} {r['recv_mb_per_round']:14.2f}")
    print()
    print("codec_overhead_ratio (slab gather, coded / identity us_per_call):")
    for codec, ratio in doc["codec_overhead"].items():
        print(f"  {codec:10s} {ratio:6.2f}x")
    print()
    tc = doc["trace_compile"]
    print(f"trace/compile at rounds={tc['rounds']} (scanned round-sets vs unrolled oracle):")
    print(f"{'codec':8s} {'variant':9s} {'trace ms':>9s} {'compile ms':>11s}")
    for r in tc["rows"]:
        print(f"{r['codec']:8s} {r['variant']:9s} {r['trace_ms']:9.1f} {r['compile_ms']:11.1f}")
    print()
    print(f"pallas launches per round-set (use_kernels=True, rounds={doc['dispatch']['rounds']}):")
    for r in doc["dispatch"]["rows"]:
        print(f"  {r['codec']:8s} launches={r['pallas_launches']} "
              f"({r['launches_per_round']:.2f}/round)")
    tm = doc["train_many_steps"]
    print(f"\nmulti-step driver ({tm['steps_per_call']} steps/call, {tm['model']}): "
          f"{tm['steps_per_s_single']:.0f} -> {tm['steps_per_s_chunked']:.0f} steps/s "
          f"({tm['speedup_many_steps']:.2f}x)")
    tl = doc["telemetry"]
    print(f"telemetry overhead (exact drt slab, {tl['rounds']} rounds): "
          f"{tl['us_disabled']:.0f}us off -> {tl['us_enabled']:.0f}us on "
          f"({tl['overhead_ratio']:.3f}x)")
    ctl = doc["control"]
    print(f"\nconsensus control (K={ctl['K']} ring, {ctl['max_rounds']} "
          f"traced rounds):")
    for r in ctl["momentum_rows"]:
        print(f"  beta={r['beta']:.1f}  final dis {r['final_disagreement']:.4f}  "
              f"rounds-to-target {r['rounds_to_fixed_target']}")
    print(f"  momentum_rounds_ratio {ctl['momentum_rounds_ratio']:.2f} "
          f"(gate <= 1.0)")
    print(f"  adaptive beta={ctl['adaptive_beta']:.1f}: mean effective rounds "
          f"{ctl['mean_effective_rounds']:.2f}/{ctl['max_rounds']} -> "
          f"round_savings {ctl['round_savings']:.2f} (gate >= 0.25)")
    _print_sparse(doc)
    rows = run(K=16)
    print()
    print(f"{'topology':10s} {'algo':>9s} {'us tree':>9s} {'us slab':>9s} {'x':>5s} "
          f"{'gthr f32':>9s} {'perm f32':>9s} {'perm int8':>9s}")
    for r in rows:
        print(f"{r['topology']:10s} {r['algorithm']:>9s} {r['us_tree']:9.0f} "
              f"{r['us_slab']:9.0f} {r['slab_speedup']:5.1f} "
              f"{r['gather_recv_mb_identity']:9.2f} {r['permute_recv_mb_identity']:9.2f} "
              f"{r['permute_recv_mb_int8']:9.2f}")
    print(f"\nwrote {os.path.abspath(args.out)}")
    return rows


if __name__ == "__main__":
    main()
