"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # only the property test needs the `test` extra; everything else runs
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.kernels import ops
from repro.kernels.ref import combine_ref, drt_dist_ref, selective_scan_ref


SHAPES = [(64,), (1000,), (128, 257), (8, 33, 5), (4096,), (32768,)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_drt_dist_matches_ref(shape, dtype):
    k1, k2 = jax.random.split(jax.random.key(hash(shape) % 2**31))
    x = jax.random.normal(k1, shape, jnp.float32).astype(dtype)
    y = jax.random.normal(k2, shape, jnp.float32).astype(dtype)
    got = ops.drt_dist(x, y)
    want = drt_dist_ref(x, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


if HAVE_HYPOTHESIS:

    @given(st.integers(1, 4096), st.integers(0, 2**31 - 1))
    @settings(deadline=None, max_examples=15)
    def test_drt_dist_property(n, seed):
        k1, k2 = jax.random.split(jax.random.key(seed))
        x = jax.random.normal(k1, (n,))
        y = jax.random.normal(k2, (n,))
        got = ops.drt_dist(x, y)
        want = drt_dist_ref(x, y)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)
        # invariants: both stats non-negative; zero iff x == y / y == 0
        assert float(got[0]) >= 0 and float(got[1]) >= 0


@pytest.mark.parametrize("N", [1, 2, 3, 8])
@pytest.mark.parametrize("D", [128, 1000, 32768 + 7])
@pytest.mark.parametrize("dtype", DTYPES)
def test_combine_matches_ref(N, D, dtype):
    key = jax.random.key(N * 1000 + D)
    a = jax.random.uniform(key, (N,))
    a = a / a.sum()
    xs = jax.random.normal(key, (N, D), jnp.float32).astype(dtype)
    got = ops.weighted_combine(a, xs)
    want = combine_ref(a, xs)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def test_combine_stochastic_preserves_constant():
    """Column-stochastic weights applied to identical inputs are a no-op."""
    N, D = 4, 513
    a = jnp.asarray([0.1, 0.2, 0.3, 0.4])
    xs = jnp.broadcast_to(jnp.arange(D, dtype=jnp.float32)[None], (N, D))
    got = ops.weighted_combine(a, xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(xs[0]), rtol=1e-6)


@pytest.mark.parametrize("B,S,di,ds,chunk", [
    (1, 16, 8, 4, 8),
    (2, 37, 32, 8, 16),
    (2, 64, 16, 16, 64),
    (1, 130, 64, 16, 32),
])
def test_selective_scan_matches_ref(B, S, di, ds, chunk):
    key = jax.random.key(S * di)
    ks = jax.random.split(key, 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, di)))
    A = -jnp.exp(jax.random.normal(ks[1], (di, ds)) * 0.2)
    Bm = jax.random.normal(ks[2], (B, S, ds))
    Cm = jax.random.normal(ks[3], (B, S, ds))
    x = jax.random.normal(ks[4], (B, S, di))
    got = ops.selective_scan(dt, A, Bm, Cm, x, chunk=chunk)
    want = jnp.stack(
        [selective_scan_ref(dt[b], A, Bm[b], Cm[b], x[b])[0] for b in range(B)]
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,H,S,hd,bq,bk", [
    (1, 2, 64, 32, 32, 32),
    (2, 3, 130, 16, 64, 64),   # ragged: padding path
    (1, 1, 256, 128, 128, 128),
    (1, 2, 100, 64, 128, 128),  # S < block
])
def test_flash_attention_kernel_matches_naive(B, H, S, hd, bq, bk):
    from repro.kernels import flash_attention

    key = jax.random.key(S)
    q = jax.random.normal(key, (B, H, S, hd))
    k = jax.random.normal(jax.random.key(1), (B, H, S, hd))
    v = jax.random.normal(jax.random.key(2), (B, H, S, hd))

    def naive(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(hd)
        mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        s = jnp.where(mask[None, None], s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    got = flash_attention(q, k, v, causal=True, blk_q=bq, blk_k=bk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(naive(q, k, v)), atol=3e-5)


def test_flash_attention_kernel_bf16():
    from repro.kernels import flash_attention

    B, H, S, hd = 1, 2, 128, 64
    q = jax.random.normal(jax.random.key(0), (B, H, S, hd), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (B, H, S, hd), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (B, H, S, hd), jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, blk_q=64, blk_k=64)
    assert got.dtype == jnp.bfloat16
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) / np.sqrt(hd)
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(mask[None, None], s, -1e30)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vf)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=3e-2
    )


# ---------------------------------------------------------------------------
# whole-slab batched combine kernels vs the per-(group, slot) references
# ---------------------------------------------------------------------------


def _slab_setup(K=4, key=jax.random.key(0)):
    from repro.core import build_slab_layout
    from repro.utils.pytree import LayerPartition

    def one(k):
        ks = jax.random.split(k, 5)
        return {
            "embed": {"w": jax.random.normal(ks[0], (4, 8)),
                      "b": jax.random.normal(ks[1], (5,))},
            "blocks": {"w": jax.random.normal(ks[2], (3, 8, 8)),
                       "g": jax.random.normal(ks[3], (3, 7)),
                       "s": jax.random.normal(ks[4], (3,))},
        }

    pK = jax.vmap(one)(jax.random.split(key, K))
    template = jax.tree.map(lambda x: x[0], pK)
    part = LayerPartition.build(template)
    layout = build_slab_layout(part, template)
    return pK, part, layout


def _region_err(a, b):
    return max(
        float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(a, b)
    )


def test_slab_combine_matches_per_slot_kernel_reference():
    """The ONE-launch whole-slab combine reproduces PR 2's per-(group, slot)
    kernel loop (interpret mode) — and both match the jnp slab combine."""
    from repro.core.consensus import _combine_slab_kernels, _combine_slab_per_slot

    K = 4
    pK, part, layout = _slab_setup(K)
    regions = layout.pack_regions(pK)
    A = jax.random.dirichlet(
        jax.random.key(3), jnp.ones(K), (part.num_layers, K)
    ).swapaxes(1, 2)  # (L, K, K) column-stochastic over axis 1
    batched = _combine_slab_kernels(layout, A, regions)
    per_slot = _combine_slab_per_slot(layout, A, regions)
    assert _region_err(batched, per_slot) < 1e-5
    assert _region_err(batched, layout.combine(A, regions)) < 1e-5
    # padding lanes stay exactly zero (later rounds' reductions rely on it)
    for grp, r in zip(layout.groups, batched):
        if grp.s_pad > grp.s:
            np.testing.assert_array_equal(np.asarray(r[..., grp.s :]), 0.0)


def test_slab_dequant_combine_matches_per_slot_kernel_reference():
    """The fused whole-slab int8 dequant+combine (per-column scales rebuilt
    in-kernel via the one-hot matmul) matches PR 2's per-(leaf, slot) fused
    kernel loop bit-for-policy (same math, reduction order only)."""
    from repro.core import packing
    from repro.core.consensus import (
        _agent_keys,
        _dequant_combine_slab_kernels,
        _dequant_combine_slab_per_slot,
    )
    from repro.comm import make_codec

    K = 4
    pK, part, layout = _slab_setup(K)
    regions = layout.pack_regions(pK)
    codec = make_codec("int8")
    keys = _agent_keys(jax.random.key(5), K)
    wire, _ = jax.vmap(
        lambda s, k: packing.slab_encode(codec, layout, s, (), k),
        in_axes=(1, 0),
        out_axes=(packing.wire_out_axes(codec), 0),
    )(regions, keys)
    A = jax.random.dirichlet(
        jax.random.key(3), jnp.ones(K), (part.num_layers, K)
    ).swapaxes(1, 2)
    A_off = A * (1.0 - jnp.eye(K))[None]
    batched = _dequant_combine_slab_kernels(layout, A_off, wire)
    per_slot = _dequant_combine_slab_per_slot(layout, A_off, wire)
    assert _region_err(batched, per_slot) < 1e-5


def test_slab_source_combine_matches_jnp():
    """out[c] = sum_n w[n, layer(c)] * srcs[n, c] — the permute engine's
    one-launch combine over stacked source slabs."""
    from repro.kernels import slab_source_combine

    _, part, layout = _slab_setup(4)
    N = 3
    srcs = jax.random.normal(jax.random.key(0), (N, layout.D))
    w = jax.random.uniform(jax.random.key(1), (N, layout.num_layers))
    w_blocks = jnp.take(w, jnp.asarray(layout.block_layer), axis=1).T
    got = slab_source_combine(w_blocks, list(srcs))
    want = jnp.einsum(
        "nc,nc->c", jnp.take(w, jnp.asarray(layout.block_layer), axis=1
        ).repeat(layout.lane, axis=1), srcs
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_use_kernels_issues_one_pallas_launch_per_round():
    """The acceptance probe: with use_kernels=True the gather round-set
    issues O(1) Pallas launches per round — exactly ONE ``slab_encode_combine``
    per coded round (encode, stats, mixing, combine AND the self term all in
    that one launch, for EVERY codec incl. top-k), and 1 per round-SET on the
    exact Gram path — independent of the model's (groups x slots) count.  The
    per-slot reference pays one per segment."""
    from repro.core import DRTConfig, gather_consensus_rounds, ring
    from repro.core.consensus import _combine_slab_per_slot
    from repro.utils.dispatch import count_pallas_launches

    K = 4
    pK, part, layout = _slab_setup(K)
    C = jnp.asarray(ring(K).c_matrix(), jnp.float32)
    n_segments = sum(g.n_slots for g in layout.groups)
    assert n_segments > 1  # the claim is non-trivial for this model

    for rounds in (3, 8):
        for codec, per_round in (
            (None, None), ("bf16", 1), ("int8", 1), ("topk:0.25", 1),
        ):
            n = count_pallas_launches(
                lambda pK, codec=codec, rounds=rounds: gather_consensus_rounds(
                    part, pK, C, DRTConfig(), rounds=rounds, codec=codec,
                    rng=jax.random.key(0) if codec else None,
                    layout=layout, use_kernels=True,
                )[0],
                pK,
            )
            if codec is None:
                assert n == 1, (codec, rounds, n)  # one combine per round-SET
            else:
                assert n == per_round * rounds, (codec, rounds, n)

    # contrast: the per-slot reference launches one kernel per segment
    A = jnp.broadcast_to(jnp.eye(K), (part.num_layers, K, K))
    regions = layout.pack_regions(pK)
    n_ref = count_pallas_launches(
        lambda r: _combine_slab_per_slot(layout, A, r), regions
    )
    assert n_ref == n_segments


# ---------------------------------------------------------------------------
# fused encode -> combine coded-round kernels (slab_codec.py)
# ---------------------------------------------------------------------------


def test_slab_quant_encode_kernel_bitwise_matches_jnp_encode():
    """The standalone int8 encode kernel (in-kernel counter RNG + one-hot
    scale reconstruction) reproduces the jnp batched slab encode bit for
    bit — same uniforms, same scales, same rounding decisions."""
    from repro.core import packing
    from repro.core.consensus import _agent_keys, _layout_col_maps
    from repro.comm import make_codec
    from repro.kernels import slab_quant_encode

    K = 4
    pK, part, layout = _slab_setup(K)
    regions = layout.pack_regions(pK)
    codec = make_codec("int8")
    keys = _agent_keys(jax.random.key(5), K)
    wire, _ = packing.slab_encode_batched(codec, layout, regions, (), keys)
    scales = packing.slab_quant_scales(codec, layout, regions)
    w0, w1 = packing.leaf_key_words(layout, keys)
    col_seg, col_leaf, col_idx = _layout_col_maps(layout)
    q_kernel = slab_quant_encode(
        scales, col_seg, col_leaf, col_idx, w0, w1, layout.join(regions)
    )
    assert q_kernel.dtype == jnp.int8
    q_jnp = layout.join(
        tuple(q.astype(jnp.float32) for q in wire.q)
    ).astype(jnp.int8)
    np.testing.assert_array_equal(np.asarray(q_kernel), np.asarray(q_jnp))
    np.testing.assert_array_equal(np.asarray(scales), np.asarray(wire.s))


@pytest.mark.parametrize("algorithm", ["drt", "classical"])
@pytest.mark.parametrize("codec", ["bf16", "f16", "int8", "topk:0.25"])
def test_slab_encode_combine_round_matches_jnp_round(codec, algorithm):
    """One fused launch per coded round == the jnp coded round (encode,
    stats, mixing, off-diagonal combine, full-precision self term), for every
    kernel-supported codec x algorithm."""
    from repro.core import DRTConfig, gather_consensus_rounds, ring

    K = 4
    pK, part, layout = _slab_setup(K)
    topo = ring(K)
    C = jnp.asarray(topo.c_matrix(), jnp.float32)
    metro = jnp.asarray(topo.metropolis(), jnp.float32)
    rng = jax.random.key(3)
    outs, As, sts = {}, {}, {}
    for use_kernels in (False, True):
        outs[use_kernels], As[use_kernels], sts[use_kernels] = (
            gather_consensus_rounds(
                part, pK, C, DRTConfig(), rounds=3, codec=codec, rng=rng,
                algorithm=algorithm, metropolis=metro, layout=layout,
                use_kernels=use_kernels,
            )
        )
    assert _region_err(
        jax.tree.leaves(outs[True]), jax.tree.leaves(outs[False])
    ) < 1e-5
    np.testing.assert_allclose(
        np.asarray(As[True]), np.asarray(As[False]), atol=1e-6
    )
    if sts[True] != ():  # top-k EF residual rides outside the kernel
        assert _region_err(
            jax.tree.leaves(sts[True]), jax.tree.leaves(sts[False])
        ) == 0.0


def test_permute_quant_encode_kernel_bitwise_matches_slab_encode():
    """The permute engine's kernel-backed per-shard int8 encode returns the
    same SlabQuant wire as the jnp per-agent slab encode."""
    from repro.core import packing
    from repro.core.consensus import _permute_quant_encode_kernels
    from repro.comm import make_codec

    pK, part, layout = _slab_setup(1)
    single = jax.tree.map(lambda x: x[0], pK)
    regions = layout.pack_regions(single)
    codec = make_codec("int8")
    key = jax.random.key(9)
    wire_jnp, _ = packing.slab_encode(codec, layout, regions, (), key)
    wire_k = _permute_quant_encode_kernels(layout, regions, codec, key)
    np.testing.assert_array_equal(
        np.asarray(wire_k.s), np.asarray(wire_jnp.s)
    )
    for a, b in zip(wire_k.q, wire_jnp.q):
        assert a.dtype == b.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_selective_scan_matches_model_impl():
    """Kernel agrees with the model-side chunked jnp implementation."""
    from repro.models.ssm import selective_scan_chunked

    B, S, di, ds = 2, 48, 16, 8
    key = jax.random.key(0)
    ks = jax.random.split(key, 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, di)))
    A = -jnp.exp(jax.random.normal(ks[1], (di, ds)) * 0.2)
    Bm = jax.random.normal(ks[2], (B, S, ds))
    Cm = jax.random.normal(ks[3], (B, S, ds))
    x = jax.random.normal(ks[4], (B, S, di))
    got = ops.selective_scan(dt, A, Bm, Cm, x, chunk=16)
    want, _ = selective_scan_chunked(dt, A, Bm, Cm, x, chunk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# analytic HBM-traffic model (PR 9): the byte case for wire-resident rounds
# ---------------------------------------------------------------------------


def test_traffic_model_wire_resident_beats_dense_and_decoded():
    """Walk the BlockSpec grids of the dense fused kernel, the wire-resident
    edge kernel and the old decoded-slab edge kernel at bench scale
    (K=64, D=271488) and check the accounting the README/regression gate
    relies on: dense ~ 3 slab passes (self x2 + parked out), wire-resident
    int8 ~ 2 + 2*rho = 2.5, old decoded path > 2x dense."""
    from repro.kernels.traffic import (
        decoded_edge_round_traffic,
        dense_round_traffic,
        edge_round_traffic,
        slab_bytes,
    )

    K, nb, L, E, dmax = 64, 2121, 17, 256, 4
    S = slab_bytes(K, nb)
    dense = dense_round_traffic(K, nb, "int8", L, n_segs=5, n_leaves=11)
    edge = edge_round_traffic(K, nb, E, dmax, "int8", L, n_segs=5)
    old = decoded_edge_round_traffic(K, nb, E, "int8", L)
    # leading-order slab-pass counts (small operands push these slightly up)
    assert 3.0 <= dense["total"] / S < 3.2
    assert 2.5 <= edge["total"] / S < 2.6
    assert old["total"] / S > 6.0
    assert edge["total"] < dense["total"]          # the K=64 hard gate
    assert old["total"] > 2.0 * dense["total"]     # what this PR removed
    # bf16 wire: 2 + 2*(1/2) = 3 passes — parity with dense, not worse
    edge_bf16 = edge_round_traffic(K, nb, E, dmax, "bf16", L)
    dense_bf16 = dense_round_traffic(K, nb, "bf16", L)
    assert edge_bf16["total"] / dense_bf16["total"] <= 1.0 + 1e-3


def test_kernel_micro_smoke_reduced_config():
    """benchmarks/kernel_micro.py keeps working against the ops signatures:
    run a reduced-size pass and check the row schema."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks import kernel_micro

    rows = kernel_micro.run(D=2048, N=2, iters=1)
    assert [r["name"] for r in rows] == ["drt_dist_2048", "combine_2x2048"]
    for r in rows:
        assert r["us_ref"] > 0 and r["us_kernel_interp"] > 0
        assert r["hbm_kernel_bytes"] < r["hbm_ref_bytes"]
