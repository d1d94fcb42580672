"""Consensus kernels on a mesh of fake CPU devices — the permute engine's
whole-slab kernels and ``shard_edge_round`` — and the pod dry-run entry
point.

Each ``_case_*`` function runs in its own process (``run_multidevice``).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from _harness import TEST_TIME_LIMIT_S, run_multidevice

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def _case_whole_slab_kernels():
    from repro.core import DRTConfig, ring
    from repro.core.consensus import PermuteConsensus, gather_consensus_rounds
    from test_distributed_engines import _agents, _assert_close, _per_agent

    K = 4
    mesh, pK, part = _agents(K)
    rng = jax.random.key(7)
    topo = ring(K)
    C = jnp.asarray(topo.c_matrix(), jnp.float32)

    for codec in (None, "int8"):
        want = jax.jit(lambda p: gather_consensus_rounds(
            part, p, C, DRTConfig(), rounds=3, codec=codec,
            rng=rng if codec else None)[0])(pK)
        eng = PermuteConsensus(part, topo, DRTConfig(), axis_name="data",
                               codec=codec, use_kernels=True)
        if codec:
            got = _per_agent(mesh, pK, lambda sq: eng(sq, rng=rng, rounds=3)[0])
        else:
            got = _per_agent(mesh, pK, lambda sq: eng(sq, rounds=3))
        _assert_close(got, want, str(codec))


def test_permute_engine_whole_slab_kernels_match_gather():
    """PermuteConsensus(use_kernels=True) routes its {self}+neighbour combine
    through the ONE-launch ``slab_source_combine`` grid (instead of one
    ``weighted_combine`` per (group, slot)); interpret-mode results match
    the gather engine for exact and int8 exchanges over multi-round sets."""
    run_multidevice(_case_whole_slab_kernels, devices=4)


def _case_shard_edge_round():
    from jax.sharding import Mesh

    from repro.core import (DRTConfig, edge_stacks_from_topology,
                            max_in_degree_from_topology, packing, ring)
    from repro.core.consensus import _layout_col_maps
    from repro.core.dynamic import csr_from_edges
    from repro.kernels import slab_edge_encode_combine
    from repro.launch.sharding import shard_edge_round
    from test_edge import _stack

    K = 8
    pK, part, layout = _stack(K=K)
    regions = layout.pack_regions(pK)
    topo = ring(K)
    edges = edge_stacks_from_topology(topo, 1)
    src, dst, w = edges.src[0], edges.dst[0], edges.w[0]
    dmax = max_in_degree_from_topology(topo)
    nbr, pos, valid, _ = csr_from_edges(src, dst, w, K, dmax)
    bl = jnp.asarray(layout.block_layer)
    slab = layout.join(regions)

    codec = packing.Int8StochasticCodec()
    keys = jax.vmap(jax.random.fold_in, (None, 0))(jax.random.key(0), jnp.arange(K))
    wire, _ = packing.slab_encode_batched(codec, layout, regions, (), keys)
    col_seg, _, _ = _layout_col_maps(layout)
    wire_ops = (layout.join(wire.q), wire.s, col_seg)
    cfg = DRTConfig()
    kw = dict(mode="int8", algorithm="drt",
              num_layers=layout.num_layers, kappa=cfg.kappa,
              N_clip=cfg.resolve_N(K), weight_mode=cfg.weight_mode,
              lane=layout.lane)

    ref = slab_edge_encode_combine(bl, slab, wire_ops, src, dst, w, nbr, pos, valid, **kw)
    mesh = Mesh(np.array(jax.devices()).reshape(8,), ("data",))
    got = shard_edge_round(mesh, bl, slab, wire_ops, src, dst, w, nbr, pos, valid, **kw)
    for r, g, n in zip(ref, got, ("out", "As", "Ae")):
        err = float(jnp.max(jnp.abs(r - g)))
        assert err == 0.0, (n, err)
    assert "data" in str(got[0].sharding.spec)


def test_shard_edge_round_matches_unsharded_kernel():
    """``shard_edge_round`` (destination-sharded self slab / CSR tables /
    output, replicated wire + edge list, per-shard dst_base offset) is
    bit-identical to the unsharded wire-resident kernel on a real 8-way
    data mesh, and the combined slab comes back sharded along agents."""
    run_multidevice(_case_shard_edge_round)


def test_dryrun_entrypoint_smoke(tmp_path):
    """The real dry-run entry point lowers+compiles one (arch x shape) on the
    production 16x16 mesh inside this subprocess (512 fake devices)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env.pop("JAX_PLATFORMS", None)
    rows = tmp_path / "dryrun.json"
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "hymba-1.5b",
         "--shape", "decode_32k", "--out", str(rows)],
        capture_output=True, text=True, env=env, timeout=TEST_TIME_LIMIT_S,
        cwd=_ROOT,
    )
    assert out.returncode == 0, out.stdout + out.stderr[-2000:]
    row = json.loads(rows.read_text())[0]
    assert row["status"] == "OK"
    assert row["chips"] == 256
    assert row["t_compute_s"] > 0 and row["hlo_flops_per_dev"] > 0
