"""Flat-slab consensus hot path: layout/round-trip properties, slab-vs-tree
engine parity per codec x topology x algorithm, codec wire bit-parity, and the
kernel-backed (``use_kernels=True``) combine in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DRTConfig,
    DecentralizedTrainer,
    TrainerConfig,
    build_slab_layout,
    gather_consensus_rounds,
    hypercube,
    make_topology,
    ring,
    slab_codec_supported,
)
from repro.core import packing
from repro.core.consensus import _agent_keys, gather_consensus_step
from repro.comm import QuantLeaf, make_codec
from repro.optim import sgd
from repro.utils.pytree import LayerPartition

ALL_CODECS = ["identity", "bf16", "f16", "int8", "topk:0.1"]


def _tree_K(K=8, key=jax.random.key(0)):
    """Multi-leaf groups with widths that force lane padding."""

    def one(k):
        ks = jax.random.split(k, 5)
        return {
            "embed": {"w": jax.random.normal(ks[0], (4, 8)),
                      "b": jax.random.normal(ks[1], (5,))},
            "blocks": {"w": jax.random.normal(ks[2], (3, 8, 8)),
                       "g": jax.random.normal(ks[3], (3, 7)),
                       "s": jax.random.normal(ks[4], (3,))},
        }

    return jax.vmap(one)(jax.random.split(key, K))


def _layout_for(pK):
    template = jax.tree.map(lambda x: x[0], pK)
    part = LayerPartition.build(template)
    return part, build_slab_layout(part, template)


def _max_err(a, b):
    return max(
        float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


# ---------------------------------------------------------------------------
# layout + pack/unpack round trip
# ---------------------------------------------------------------------------


def test_layout_layer_slices_are_lane_padded_and_cover_slab():
    pK = _tree_K()
    part, layout = _layout_for(pK)
    assert layout.num_layers == part.num_layers
    assert layout.D % packing.LANES == 0
    covered = np.zeros(layout.D, bool)
    for (s, e), size in zip(layout.layer_slices, layout.layer_sizes):
        assert s % packing.LANES == 0 and e % packing.LANES == 0
        assert 0 < size <= e - s
        assert not covered[s:e].any()  # segments are disjoint
        covered[s:e] = True
    assert covered.all()  # ...and tile the slab exactly
    # layer p of group g is slot p - layer0 of that group's region
    for grp in layout.groups:
        for j in range(grp.n_slots):
            s, e = layout.layer_slices[grp.layer0 + j]
            assert (s, e) == (
                grp.col0 + j * grp.s_pad,
                grp.col0 + (j + 1) * grp.s_pad,
            )


def test_pack_unpack_round_trip_exact_with_agent_axis():
    pK = _tree_K()
    _, layout = _layout_for(pK)
    slab = layout.pack(pK)
    assert slab.shape == (8, layout.D) and slab.dtype == jnp.float32
    back = layout.unpack(slab, like=pK)
    assert jax.tree.structure(back) == jax.tree.structure(pK)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(pK)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pack_unpack_mixed_dtypes_and_passthrough():
    """bf16/f16 round-trip exactly through the f32 slab; integer leaves are
    not packed and pass through unpack verbatim."""
    tree = {
        "embed": {"w": jax.random.normal(jax.random.key(0), (4, 8)).astype(jnp.bfloat16),
                  "idx": jnp.arange(6, dtype=jnp.int32)},
        "blocks": {"w": jax.random.normal(jax.random.key(1), (3, 8, 8)).astype(jnp.float16),
                   "g": jax.random.normal(jax.random.key(2), (3, 7))},
    }
    part = LayerPartition.build(tree)
    layout = build_slab_layout(part, tree)
    slab = layout.pack(tree)
    back = layout.unpack(slab, like=tree)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # padding columns are zero (reductions over segments stay exact)
    dead = np.ones(layout.D, bool)
    for grp in layout.groups:
        for j in range(grp.n_slots):
            s0 = grp.col0 + j * grp.s_pad
            dead[s0 : s0 + grp.s] = False
    np.testing.assert_array_equal(np.asarray(slab)[dead], 0.0)


def test_pack_rejects_wrong_shapes():
    pK = _tree_K()
    _, layout = _layout_for(pK)
    bad = jax.tree.map(lambda x: x, pK)
    bad["embed"]["w"] = jnp.zeros((8, 4, 9))
    with pytest.raises(ValueError):
        layout.pack(bad)


# ---------------------------------------------------------------------------
# segment reductions vs the per-leaf oracle
# ---------------------------------------------------------------------------


def test_split_join_round_trip_and_region_shapes():
    pK = _tree_K()
    _, layout = _layout_for(pK)
    slab = layout.pack(pK)
    regions = layout.split(slab)
    assert len(regions) == len(layout.groups)
    for grp, region in zip(layout.groups, regions):
        # slot-major: scan-slot axis leading, agent batch axis second
        assert region.shape == (grp.n_slots, 8, grp.s_pad)
    np.testing.assert_array_equal(np.asarray(layout.join(regions)), np.asarray(slab))
    # pack_regions agrees with split(pack(...))
    for a, b in zip(layout.pack_regions(pK), regions):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_slab_stats_match_tree_oracle():
    pK = _tree_K()
    part, layout = _layout_for(pK)
    regions = layout.pack_regions(pK)
    d2_t, n2_t = part.pairwise_sq_dists(pK)
    d2_s, n2_s = layout.pairwise_sq_dists(regions)
    np.testing.assert_allclose(np.asarray(d2_s), np.asarray(d2_t), rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(n2_s), np.asarray(n2_t), rtol=1e-5, atol=1e-4)
    # per-agent layer norms (slot-major: layers lead, agents trail)
    n_s = layout.layer_sq_norms(regions)  # (L, K)
    n_t = part.agent_sq_norms(pK)  # (L, K)
    np.testing.assert_allclose(np.asarray(n_s), np.asarray(n_t), rtol=1e-5, atol=1e-4)


def test_slab_combine_matches_tree_oracle():
    pK = _tree_K()
    part, layout = _layout_for(pK)
    regions = layout.pack_regions(pK)
    A = jax.random.dirichlet(
        jax.random.key(3), jnp.ones(8), (part.num_layers, 8)
    ).swapaxes(1, 2)  # (L, K, K) column-stochastic over axis 1
    want = part.combine(A, pK)
    got = layout.unpack_regions(layout.combine(A, regions), like=pK)
    assert _max_err(got, want) < 1e-5


# ---------------------------------------------------------------------------
# codec fast paths: wire bit-parity with the tree codecs
# ---------------------------------------------------------------------------


def test_int8_slab_wire_bitwise_matches_tree_codec():
    """Same per-(leaf, slot) scales, same per-leaf uniform draws -> the slab
    int8 wire decodes bit-identically to the tree codec's."""
    K = 8
    pK = _tree_K(K)
    _, layout = _layout_for(pK)
    regions = layout.pack_regions(pK)
    codec = make_codec("int8")
    keys = _agent_keys(jax.random.key(5), K)
    wire_t, _ = jax.vmap(codec.encode)(pK, (), keys)
    dec_t = jax.vmap(codec.decode)(wire_t)
    wire_s, _ = jax.vmap(
        lambda s, k: packing.slab_encode(codec, layout, s, (), k),
        in_axes=(1, 0),
        out_axes=(packing.wire_out_axes(codec), 0),
    )(regions, keys)
    assert all(q.dtype == jnp.int8 for q in wire_s.q)
    dec_s = packing.slab_decode(codec, layout, wire_s)
    np.testing.assert_array_equal(
        np.asarray(layout.pack(dec_t)), np.asarray(layout.join(dec_s))
    )
    # scales match the tree codec's per-leaf/per-slot absmax granularity
    leaves_t = jax.tree.leaves(
        wire_t, is_leaf=lambda x: isinstance(x, QuantLeaf)
    )
    tree_scales = sorted(
        float(s) for w in leaves_t if isinstance(w, QuantLeaf)
        for s in np.asarray(w.s[0]).ravel()
    )
    slab_scales = sorted(float(s) for s in np.asarray(wire_s.s[0]))
    np.testing.assert_allclose(slab_scales, tree_scales, rtol=0, atol=0)


def test_topk_slab_wire_and_residual_bitwise_match_tree_codec():
    K = 8
    pK = _tree_K(K)
    _, layout = _layout_for(pK)
    regions = layout.pack_regions(pK)
    codec = make_codec("topk:0.1")
    keys = _agent_keys(jax.random.key(5), K)
    st_t = jax.vmap(codec.init_state)(pK)
    wire_t, st_t2 = jax.vmap(codec.encode)(pK, st_t, keys)
    res0 = tuple(
        jnp.zeros((g.n_slots, K, g.s_pad)) for g in layout.groups
    )
    wire_s, res1 = jax.vmap(
        lambda s, st, k: packing.slab_encode(codec, layout, s, st, k),
        in_axes=(1, 1, 0),
        out_axes=(1, 1),
    )(regions, res0, keys)
    np.testing.assert_array_equal(
        np.asarray(layout.pack(wire_t)), np.asarray(layout.join(wire_s))
    )
    np.testing.assert_array_equal(
        np.asarray(layout.pack(st_t2)), np.asarray(layout.join(res1))
    )
    # second round consumes the residual identically
    wire_t3, st_t3 = jax.vmap(codec.encode)(pK, st_t2, keys)
    wire_s3, res3 = jax.vmap(
        lambda s, st, k: packing.slab_encode(codec, layout, s, st, k),
        in_axes=(1, 1, 0),
        out_axes=(1, 1),
    )(regions, res1, keys)
    np.testing.assert_array_equal(
        np.asarray(layout.pack(st_t3)), np.asarray(layout.join(res3))
    )


def test_slab_codec_support_matrix():
    for name in ALL_CODECS:
        assert slab_codec_supported(make_codec(name))

    class Weird:
        name = "weird"
        stateful = False
        needs_rng = False

    assert not slab_codec_supported(Weird())


# ---------------------------------------------------------------------------
# engine parity: slab vs tree, per codec x topology x algorithm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("topo_name", ["ring", "hypercube", "torus2d"])
@pytest.mark.parametrize("algorithm", ["drt", "classical"])
@pytest.mark.parametrize("codec", [None] + ALL_CODECS)
def test_slab_vs_tree_engine_parity(topo_name, algorithm, codec):
    """The slab hot path reproduces the per-leaf oracle for every codec,
    topology and algorithm over a full 3-round set (identical wire values by
    construction; residual float reassociation only)."""
    K = 4
    pK = _tree_K(K)
    part, layout = _layout_for(pK)
    topo = make_topology(topo_name, K)
    C = jnp.asarray(topo.c_matrix(), jnp.float32)
    metro = jnp.asarray(topo.metropolis(), jnp.float32)
    rng = jax.random.key(11)
    kw = dict(
        rounds=3, algorithm=algorithm, metropolis=metro, codec=codec, rng=rng
    )
    want, A_t, st_t = gather_consensus_rounds(part, pK, C, DRTConfig(), path="tree", **kw)
    got, A_s, st_s = gather_consensus_rounds(
        part, pK, C, DRTConfig(), path="slab", layout=layout, **kw
    )
    tol = 2e-4 if codec == "f16" else 5e-6
    assert _max_err(got, want) < tol, (topo_name, algorithm, codec)
    np.testing.assert_allclose(np.asarray(A_s), np.asarray(A_t), atol=1e-4)
    if jax.tree.leaves(st_t):  # stateful codec: EF residual parity too
        assert _max_err(st_s, st_t) < tol


def test_classical_identity_slab_parity_is_bitwise_on_wire_values():
    """With a static mixing matrix the slab and tree paths consume identical
    inputs; the combined outputs agree to reduction-order noise and the
    mixing matrices are identical."""
    K = 4
    pK = _tree_K(K)
    part, layout = _layout_for(pK)
    topo = ring(K)
    C = jnp.asarray(topo.c_matrix(), jnp.float32)
    metro = jnp.asarray(topo.metropolis(), jnp.float32)
    want, A_t, _ = gather_consensus_rounds(
        part, pK, C, DRTConfig(), rounds=1, algorithm="classical",
        metropolis=metro, path="tree",
    )
    got, A_s, _ = gather_consensus_rounds(
        part, pK, C, DRTConfig(), rounds=1, algorithm="classical",
        metropolis=metro, path="slab", layout=layout,
    )
    np.testing.assert_array_equal(np.asarray(A_s), np.asarray(A_t))
    assert _max_err(got, want) < 1e-6


def test_unsupported_codec_falls_back_to_tree_path():
    """A custom codec without a slab fast path must still work through
    gather_consensus_rounds (automatic tree fallback)."""
    import dataclasses as dc

    from repro.comm import CastCodec

    @dc.dataclass(frozen=True)
    class MyCast(CastCodec):
        pass

    codec = MyCast(dtype=jnp.bfloat16, name="mycast")

    class Opaque:
        """Deliberately not a built-in codec class."""

        name = "opaque-bf16"
        stateful = False
        needs_rng = False

        def init_state(self, template):
            return ()

        def encode(self, tree, state=(), key=None):
            return jax.tree.map(lambda x: x.astype(jnp.bfloat16), tree), state

        def decode(self, wire):
            return jax.tree.map(lambda x: x.astype(jnp.float32), wire)

        def wire_bytes(self, template):
            return 0

    K = 4
    pK = _tree_K(K)
    part, layout = _layout_for(pK)
    C = jnp.asarray(ring(K).c_matrix(), jnp.float32)
    assert not slab_codec_supported(Opaque())
    got, _, _ = gather_consensus_rounds(
        part, pK, C, DRTConfig(), rounds=1, codec=Opaque(), rng=jax.random.key(0),
        path="slab",
    )
    want, _, _ = gather_consensus_rounds(
        part, pK, C, DRTConfig(), rounds=1, codec="bf16", rng=jax.random.key(0),
        path="tree",
    )
    assert _max_err(got, want) < 1e-6  # same semantics via the fallback


def test_non_float_templates_fall_back_to_tree_path():
    """A tree with an int-only top-level group (or any non-float leaf) must
    take the per-leaf oracle on BOTH engines: the tree path casts non-float
    leaves into the distance stats while the slab excludes them, so running
    the slab there would silently diverge (and an int-only group would
    misalign every later group's gram rows)."""
    K = 4

    def one(k):
        k1, k2 = jax.random.split(k)
        return {
            "embed": {"w": jax.random.normal(k1, (4, 8))},
            "counters": {"n": jnp.arange(3, dtype=jnp.int32)},
            "zblocks": {"w": jax.random.normal(k2, (3, 8, 8))},
        }

    pK = jax.vmap(one)(jax.random.split(jax.random.key(0), K))
    template = jax.tree.map(lambda x: x[0], pK)
    assert not packing.slab_template_supported(template)
    part = LayerPartition.build(template)
    # the layout build itself refuses the misaligned group...
    with pytest.raises(ValueError, match="no float leaves"):
        build_slab_layout(part, template)
    # ...and the engine silently takes the tree path, matching the oracle
    C = jnp.asarray(ring(K).c_matrix(), jnp.float32)
    got, A_s, _ = gather_consensus_rounds(part, pK, C, DRTConfig(), rounds=2, path="slab")
    want, A_t, _ = gather_consensus_rounds(part, pK, C, DRTConfig(), rounds=2, path="tree")
    assert _max_err(got, want) == 0.0
    np.testing.assert_array_equal(np.asarray(A_s), np.asarray(A_t))


def test_zero_rounds_rejected_on_both_paths():
    """A zero/negative round budget is a caller bug (the old silent no-op hid
    misconfigured round counts); the engine refuses loudly on every path."""
    K = 4
    pK = _tree_K(K)
    part, layout = _layout_for(pK)
    C = jnp.asarray(ring(K).c_matrix(), jnp.float32)
    for path in ("slab", "tree"):
        for rounds in (0, -1):
            with pytest.raises(ValueError, match="rounds >= 1"):
                gather_consensus_rounds(
                    part, pK, C, DRTConfig(), rounds=rounds,
                    metropolis=jnp.asarray(ring(K).metropolis(), jnp.float32),
                    path=path, layout=layout,
                )


def test_topk_residual_stays_f32_for_bf16_params():
    """The slab path must not truncate the f32 error-feedback residual to the
    parameter dtype (bf16 here) — the tree codec keeps it f32."""
    K = 4

    def one(k):
        k1, k2 = jax.random.split(k)
        return {
            "embed": {"w": jax.random.normal(k1, (4, 8)).astype(jnp.bfloat16)},
            "blocks": {"w": jax.random.normal(k2, (3, 8, 8)).astype(jnp.bfloat16)},
        }

    pK = jax.vmap(one)(jax.random.split(jax.random.key(0), K))
    part, layout = _layout_for(pK)
    C = jnp.asarray(ring(K).c_matrix(), jnp.float32)
    new, _, st = gather_consensus_rounds(
        part, pK, C, DRTConfig(), rounds=2, codec="topk:0.25",
        rng=jax.random.key(0), path="slab", layout=layout,
    )
    for p, r in zip(jax.tree.leaves(new), jax.tree.leaves(st)):
        assert p.dtype == jnp.bfloat16  # params keep their dtype
        assert r.dtype == jnp.float32  # residual keeps full precision
    # second round-set consumes the f32 state without a dtype mismatch
    gather_consensus_rounds(
        part, pK, C, DRTConfig(), rounds=1, codec="topk:0.25",
        codec_state=st, rng=jax.random.key(1), path="slab", layout=layout,
    )


def test_trainer_slab_and_tree_paths_agree():
    """Trainer-level parity: identical consensus results (and EF residuals)
    from consensus_path='slab' and 'tree' over a multi-step run."""
    K, dim = 8, 6
    targets = jax.random.normal(jax.random.key(5), (K, dim))

    def init_fn(key):
        return {"embed": {"w": jnp.zeros((dim,))}, "blocks": {"w": jnp.zeros((2, dim))}}

    def loss_fn(params, batch, rng):
        return jnp.sum((params["embed"]["w"] - batch) ** 2) + jnp.sum(
            (params["blocks"]["w"] - batch[None]) ** 2
        )

    outs = {}
    for path in ("slab", "tree"):
        tr = DecentralizedTrainer(
            loss_fn, init_fn, sgd(0.05), ring(K),
            TrainerConfig(algorithm="drt", consensus_steps=3, codec="topk:0.25",
                          consensus_path=path),
        )
        st = tr.init(jax.random.key(0))
        step = jax.jit(tr.local_step)
        cons = jax.jit(tr.consensus)
        for i in range(10):
            st, _ = step(st, targets, jax.random.key(i))
            st, _ = cons(st)
        outs[path] = st
    assert _max_err(outs["slab"].params, outs["tree"].params) < 1e-5
    assert _max_err(outs["slab"].comm, outs["tree"].comm) < 1e-5


# ---------------------------------------------------------------------------
# engine parity under a CHANGING per-round mixing matrix (dynamic schedules)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("algorithm", ["drt", "classical"])
@pytest.mark.parametrize("codec", [None] + ALL_CODECS)
def test_slab_vs_tree_parity_under_dynamic_mixing(algorithm, codec):
    """The slab hot path (incl. the generalized Gram recurrence
    G' = A_t^T G A_t for exact exchanges) reproduces the per-leaf oracle when
    EVERY round mixes over a different graph — periodic ring<->hypercube with
    agent churn, per-round (C_t, metropolis_t) stacks."""
    from repro.core import ChurnSchedule, PeriodicSchedule

    K = 4
    pK = _tree_K(K)
    part, layout = _layout_for(pK)
    sched = ChurnSchedule(
        PeriodicSchedule((ring(K), hypercube(K))), agent_drop=0.25, seed=3
    )
    C_s, M_s = sched.mixing_stacks(1, 3)
    rng = jax.random.key(11)
    kw = dict(rounds=3, algorithm=algorithm, metropolis=M_s, codec=codec, rng=rng)
    want, A_t, st_t = gather_consensus_rounds(
        part, pK, C_s, DRTConfig(), path="tree", **kw
    )
    got, A_s, st_s = gather_consensus_rounds(
        part, pK, C_s, DRTConfig(), path="slab", layout=layout, **kw
    )
    tol = 2e-4 if codec == "f16" else 5e-6
    assert _max_err(got, want) < tol, (algorithm, codec)
    np.testing.assert_allclose(np.asarray(A_s), np.asarray(A_t), atol=1e-4)
    if jax.tree.leaves(st_t):  # stateful codec: EF residual parity too
        assert _max_err(st_s, st_t) < tol


def test_per_round_stack_shape_is_validated():
    K = 4
    pK = _tree_K(K)
    part, layout = _layout_for(pK)
    C3 = jnp.broadcast_to(
        jnp.asarray(ring(K).c_matrix(), jnp.float32), (2, K, K)
    )
    with pytest.raises(ValueError, match="per-round C stack"):
        gather_consensus_rounds(
            part, pK, C3, DRTConfig(), rounds=3, path="slab", layout=layout
        )


def test_dynamic_stacks_match_round_by_round_oracle():
    """Driving the round-set with stacked (C_t, metropolis_t) equals calling
    the single-round oracle with each round's matrices in sequence."""
    from repro.core import PeriodicSchedule

    K = 4
    pK = _tree_K(K)
    part, layout = _layout_for(pK)
    sched = PeriodicSchedule((ring(K), hypercube(K)))
    C_s, M_s = sched.mixing_stacks(0, 3)
    for algorithm in ("drt", "classical"):
        got, A_last, _ = gather_consensus_rounds(
            part, pK, C_s, DRTConfig(), rounds=3, algorithm=algorithm,
            metropolis=M_s, path="slab", layout=layout,
        )
        want = pK
        for r in range(3):
            want, A_r = gather_consensus_step(
                part, want, C_s[r], DRTConfig(), algorithm=algorithm,
                metropolis=M_s[r],
            )
        assert _max_err(got, want) < 5e-6, algorithm
        np.testing.assert_allclose(np.asarray(A_last), np.asarray(A_r), atol=1e-5)


# ---------------------------------------------------------------------------
# scanned round-sets: bit-parity with the unrolled oracle
# ---------------------------------------------------------------------------


def _assert_bitwise(a, b, msg):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), msg
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=msg)


@pytest.mark.parametrize("dynamic", [False, True])
@pytest.mark.parametrize("algorithm", ["drt", "classical"])
@pytest.mark.parametrize("codec", [None] + ALL_CODECS)
def test_scanned_rounds_bitwise_match_unrolled_oracle(dynamic, algorithm, codec):
    """The lax.scan round-set (trace/compile O(1) in rounds) is BIT-identical
    to the unrolled Python-loop oracle for every codec x algorithm x
    static/dynamic schedule — combined params, the last mixing matrix and
    any EF residual alike.  Covers all three slab sub-paths (exact Gram
    recurrence, coded rounds, and — via the fallback matrix below — the tree
    oracle)."""
    from repro.core import ChurnSchedule, PeriodicSchedule

    K = 4
    pK = _tree_K(K)
    part, layout = _layout_for(pK)
    if dynamic:
        sched = ChurnSchedule(
            PeriodicSchedule((ring(K), hypercube(K))), agent_drop=0.25, seed=3
        )
        C, metro = sched.mixing_stacks(1, 3)
    else:
        topo = ring(K)
        C = jnp.asarray(topo.c_matrix(), jnp.float32)
        metro = jnp.asarray(topo.metropolis(), jnp.float32)
    kw = dict(
        rounds=3, algorithm=algorithm, metropolis=metro, codec=codec,
        rng=jax.random.key(11) if codec is not None else None, layout=layout,
    )
    scanned = jax.jit(
        lambda pK: gather_consensus_rounds(part, pK, C, DRTConfig(), **kw)
    )(pK)
    unrolled = jax.jit(
        lambda pK: gather_consensus_rounds(
            part, pK, C, DRTConfig(), unroll=True, **kw
        )
    )(pK)
    msg = f"{algorithm}/{codec}/dynamic={dynamic}"
    _assert_bitwise(scanned[0], unrolled[0], msg)  # combined params
    np.testing.assert_array_equal(
        np.asarray(scanned[1]), np.asarray(unrolled[1]), err_msg=msg
    )  # A_last
    _assert_bitwise(scanned[2], unrolled[2], msg)  # codec state


@pytest.mark.parametrize("codec", [None, "int8", "topk:0.1"])
def test_tree_path_scanned_bitwise_matches_unrolled(codec):
    """The per-leaf tree oracle's round loop is ALSO scanned — parity with
    its own unrolled form (the reference of the reference)."""
    K = 4
    pK = _tree_K(K)
    part, _ = _layout_for(pK)
    C = jnp.asarray(ring(K).c_matrix(), jnp.float32)
    kw = dict(
        rounds=3, codec=codec,
        rng=jax.random.key(5) if codec is not None else None, path="tree",
    )
    scanned = jax.jit(
        lambda pK: gather_consensus_rounds(part, pK, C, DRTConfig(), **kw)
    )(pK)
    unrolled = jax.jit(
        lambda pK: gather_consensus_rounds(
            part, pK, C, DRTConfig(), unroll=True, **kw
        )
    )(pK)
    _assert_bitwise(scanned[0], unrolled[0], str(codec))
    _assert_bitwise(scanned[2], unrolled[2], str(codec))


def test_scanned_rounds_trace_is_sublinear_in_rounds():
    """The scanned path's jaxpr size must be (near-)flat in `rounds` while
    the unrolled oracle's grows linearly — the structural form of the
    trace/compile-cost claim, asserted without wall-clock noise."""
    K = 4
    pK = _tree_K(K)
    part, layout = _layout_for(pK)
    C = jnp.asarray(ring(K).c_matrix(), jnp.float32)

    def eqn_count(rounds, unroll):
        jaxpr = jax.make_jaxpr(
            lambda pK: gather_consensus_rounds(
                part, pK, C, DRTConfig(), rounds=rounds, codec="int8",
                rng=jax.random.key(0), layout=layout, unroll=unroll,
            )[0]
        )(pK)
        return len(jaxpr.jaxpr.eqns)

    scan2, scan8 = eqn_count(2, False), eqn_count(8, False)
    unroll2, unroll8 = eqn_count(2, True), eqn_count(8, True)
    assert scan8 == scan2  # O(1): the body traces once whatever the length
    assert unroll8 > unroll2  # the oracle pays per round
    assert scan8 < unroll8


# ---------------------------------------------------------------------------
# kernel-backed combine (interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("codec", [None, "bf16", "int8"])
def test_use_kernels_gather_parity_interpret(codec):
    """use_kernels=True routes the slab combine through the Pallas
    weighted_combine (and dequant_combine for int8) kernels; interpret-mode
    results match the jnp slab path."""
    K = 4
    pK = _tree_K(K)
    part, layout = _layout_for(pK)
    C = jnp.asarray(ring(K).c_matrix(), jnp.float32)
    rng = jax.random.key(2)
    ref, A_r, _ = gather_consensus_rounds(
        part, pK, C, DRTConfig(), rounds=1, codec=codec, rng=rng, layout=layout
    )
    ker, A_k, _ = gather_consensus_rounds(
        part, pK, C, DRTConfig(), rounds=1, codec=codec, rng=rng, layout=layout,
        use_kernels=True,
    )
    assert _max_err(ker, ref) < 1e-5
    np.testing.assert_allclose(np.asarray(A_k), np.asarray(A_r), atol=1e-6)


def test_use_kernels_trainer_end_to_end():
    K, dim = 4, 6
    targets = jax.random.normal(jax.random.key(5), (K, dim))

    def init_fn(key):
        return {"embed": {"w": jnp.zeros((dim,))}, "blocks": {"w": jnp.zeros((2, dim))}}

    def loss_fn(params, batch, rng):
        return jnp.sum((params["embed"]["w"] - batch) ** 2) + jnp.sum(
            (params["blocks"]["w"] - batch[None]) ** 2
        )

    sts = {}
    for use_kernels in (False, True):
        tr = DecentralizedTrainer(
            loss_fn, init_fn, sgd(0.05), ring(K),
            TrainerConfig(consensus_steps=2, use_kernels=use_kernels),
        )
        st = tr.init(jax.random.key(0))
        for i in range(3):
            st, _ = tr.local_step(st, targets, jax.random.key(i))
            st, _ = tr.consensus(st)
        sts[use_kernels] = st
    assert _max_err(sts[True].params, sts[False].params) < 1e-5


# ---------------------------------------------------------------------------
# fused batched encode: bit-parity with the two-phase per-agent oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("codec", ["bf16", "f16", "int8", "topk:0.1", "topk:0.1:0"])
def test_batched_encode_bitwise_matches_two_phase_oracle(codec):
    """``slab_encode_batched`` (the gather engine's fused coded-round encode)
    produces the SAME wire — values, scales, EF residual — as vmapping the
    per-agent two-phase ``slab_encode`` over the agent axis."""
    K = 8
    pK = _tree_K(K)
    _, layout = _layout_for(pK)
    regions = layout.pack_regions(pK)
    c = make_codec(codec)
    keys = _agent_keys(jax.random.key(5), K)
    wax = packing.wire_out_axes(c)
    if c.stateful:
        st0 = tuple(
            jnp.zeros((g.n_slots, K, g.s_pad), jnp.float32)
            for g in layout.groups
        )
        wire_o, st_o = jax.vmap(
            lambda s, st, k: packing.slab_encode(c, layout, s, st, k),
            in_axes=(1, 1, 0), out_axes=(wax, 1),
        )(regions, st0, keys)
        wire_b, st_b = packing.slab_encode_batched(c, layout, regions, st0, keys)
        for a, b in zip(st_o, st_b):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    else:
        wire_o, _ = jax.vmap(
            lambda s, k: packing.slab_encode(c, layout, s, (), k),
            in_axes=(1, 0), out_axes=(wax, 0),
        )(regions, keys)
        wire_b, _ = packing.slab_encode_batched(c, layout, regions, (), keys)
    for a, b in zip(jax.tree.leaves(wire_o), jax.tree.leaves(wire_b)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # decode agrees too (batched decode is the same function)
    dec_o = packing.slab_decode(c, layout, wire_o)
    dec_b = packing.slab_decode(c, layout, wire_b)
    for a, b in zip(dec_o, dec_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_layout_col_maps_cover_every_column():
    """col_leaf/col_idx (the kernels' in-kernel RNG maps) address exactly the
    element the pack places in each column."""
    pK = _tree_K(2)
    _, layout = _layout_for(pK)
    assert layout.col_leaf.shape == (layout.D,)
    assert layout.col_idx.shape == (layout.D,)
    # reconstruct the slab from the maps: for each column, fetch the
    # template element (leaf, idx) and compare against a real pack
    template = jax.tree.map(lambda x: x[0], pK)
    leaves = jax.tree.leaves(template)
    slab = np.asarray(layout.pack(template))
    flat = [np.asarray(l).reshape(-1) for l in leaves]
    for grp in layout.groups:
        for j in range(grp.n_slots):
            base = grp.col0 + j * grp.s_pad
            for plan in grp.float_leaves:
                cols = np.arange(base + plan.col0, base + plan.col0 + plan.width)
                got = flat[layout.col_leaf[cols[0]]][layout.col_idx[cols]]
                np.testing.assert_array_equal(slab[cols], got.astype(np.float32))


def test_col_maps_match_static_tables():
    """``SlabLayout.col_maps`` (built from iotas on device, no D-sized
    constants) equals the static numpy column tables the kernels were
    specified against."""
    pK = _tree_K(4)
    _, layout = _layout_for(pK)
    seg, leaf, idx = (np.asarray(m).reshape(-1) for m in jax.jit(layout.col_maps)())
    np.testing.assert_array_equal(seg, layout.col_scale_seg)
    np.testing.assert_array_equal(leaf, layout.col_leaf)
    np.testing.assert_array_equal(idx, layout.col_idx)
