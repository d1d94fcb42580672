"""Sparse edge-list consensus path (PR 7): parity of ``path="edge"`` with
the dense slab path across codec x algorithm x schedule, the CSR
(gather-only) combine vs the scatter oracle, edge-stack/mixing-stack
bit-consistency, padding inertness, isolated-agent identity, EF residual
and telemetry parity, and the one-launch-per-round contract of the fused
``slab_edge_combine`` kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ChurnSchedule,
    DRTConfig,
    PeriodicSchedule,
    RandomGossipSchedule,
    StaticSchedule,
    build_slab_layout,
    edge_stacks_from_topology,
    gather_consensus_rounds,
    hypercube,
    make_topology,
    max_in_degree_from_topology,
    ring,
)
from repro.core.dynamic import EdgeStacks, csr_from_edges
from repro.utils.pytree import LayerPartition

K = 8
ROUNDS = 3


def _stack(K=K, key=jax.random.key(0)):
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
    return pK, part, build_slab_layout(part, template)


def _schedules():
    return {
        "static_ring": StaticSchedule(ring(K)),
        "static_chain": StaticSchedule(make_topology("chain", K)),
        "gossip": RandomGossipSchedule(K, p=0.4, seed=3),
        "churn": ChurnSchedule(
            PeriodicSchedule((ring(K), hypercube(K))), agent_drop=0.25,
            edge_drop=0.1, seed=5,
        ),
    }


def _run(pK, part, layout, sched, *, path, codec, algorithm, rounds=ROUNDS,
         max_in_degree="auto", obs=None):
    C, metro = sched.mixing_stacks(0, rounds)
    kw = {}
    if path == "edge":
        kw["edges"] = sched.edge_stacks(0, rounds)
        kw["max_in_degree"] = (
            sched.max_in_degree if max_in_degree == "auto" else max_in_degree
        )
    return gather_consensus_rounds(
        part, pK, C, DRTConfig(), rounds=rounds, algorithm=algorithm,
        metropolis=metro, codec=codec,
        rng=jax.random.key(7) if codec is not None else None,
        layout=layout, path=path, obs=obs, **kw,
    )


def _max_err(a, b):
    return max(
        float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


# ---------------------------------------------------------------------------
# the sparse view is bit-consistent with the dense stacks
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", list(_schedules()))
def test_edge_stacks_realize_the_same_graphs_as_the_dense_stacks(name):
    sched = _schedules()[name]
    edges = sched.edge_stacks(0, 6)
    for t in range(6):
        adj = np.asarray(sched.topology_at(t).adjacency, dtype=bool)
        np.fill_diagonal(adj, False)
        src = np.asarray(edges.src[t])
        dst = np.asarray(edges.dst[t])
        w = np.asarray(edges.w[t])
        real = w > 0
        realized = np.zeros_like(adj)
        realized[dst[real], src[real]] = True
        np.testing.assert_array_equal(realized, adj)
        # canonical (dst, src) sort => each destination's in-edges contiguous
        order = np.lexsort((src[real], dst[real]))
        assert (order == np.arange(order.size)).all()
        # padding is inert by construction: src = dst = 0, w = 0
        assert (src[~real] == 0).all() and (dst[~real] == 0).all()


@pytest.mark.parametrize("name", list(_schedules()))
def test_max_in_degree_bounds_every_round(name):
    sched = _schedules()[name]
    dmax = sched.max_in_degree
    edges = sched.edge_stacks(0, 8)
    for t in range(8):
        dst = np.asarray(edges.dst[t])
        real = np.asarray(edges.w[t]) > 0
        if real.any():
            assert np.bincount(dst[real]).max() <= dmax


def test_max_in_degree_from_topology_matches_adjacency():
    for name, want in (("ring", 2), ("chain", 2), ("full", K - 1)):
        assert max_in_degree_from_topology(make_topology(name, K)) == want


# ---------------------------------------------------------------------------
# csr_from_edges: in-graph CSR tables from the sorted edge list
# ---------------------------------------------------------------------------


def test_csr_from_edges_tables_match_numpy_reference():
    sched = _schedules()["gossip"]
    edges = sched.edge_stacks(0, 4)
    dmax = sched.max_in_degree
    for t in range(4):
        src, dst, w = edges.src[t], edges.dst[t], edges.w[t]
        nbr, pos, valid, rank = jax.jit(
            lambda s, d, ww: csr_from_edges(s, d, ww, K, dmax)
        )(src, dst, w)
        nbr, pos, valid = map(np.asarray, (nbr, pos, valid))
        rank = np.asarray(rank)
        s_np, d_np, w_np = map(np.asarray, (src, dst, w))
        real = w_np > 0
        for k in range(K):
            ins = sorted(s_np[real & (d_np == k)])
            deg = len(ins)
            assert valid[k, :deg].all() and not valid[k, deg:].any()
            assert list(nbr[k, :deg]) == ins  # (dst, src)-sorted edge list
        # rank maps edge e -> its CSR column; pos maps (k, j) -> edge index
        for e in np.nonzero(real)[0]:
            k, j = d_np[e], rank[e]
            assert pos[k, j] == e and nbr[k, j] == s_np[e]


# ---------------------------------------------------------------------------
# parity matrix: edge path vs dense slab path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", list(_schedules()))
@pytest.mark.parametrize("algorithm", ["drt", "classical"])
@pytest.mark.parametrize("codec", [None, "bf16", "int8", "topk:0.25"])
def test_edge_matches_dense_across_codec_algorithm_schedule(
    name, algorithm, codec
):
    pK, part, layout = _stack()
    sched = _schedules()[name]
    dense = _run(pK, part, layout, sched, path="slab", codec=codec,
                 algorithm=algorithm)
    edge = _run(pK, part, layout, sched, path="edge", codec=codec,
                algorithm=algorithm)
    # same rng => bit-identical wire; the paths differ only in stats/combine
    # contraction order (dense Gram/matmul vs per-edge gathers), so the
    # outputs agree to f32 reduction-order noise
    assert _max_err(dense[0], edge[0]) < 2e-4, (name, algorithm, codec)
    if codec == "topk:0.25":
        # stateful codec: the carried EF residual must agree too
        assert _max_err(dense[2], edge[2]) < 2e-4, (name, algorithm)


def test_csr_combine_matches_scatter_oracle():
    pK, part, layout = _stack()
    for name in ("static_chain", "gossip"):
        sched = _schedules()[name]
        csr = _run(pK, part, layout, sched, path="edge", codec=None,
                   algorithm="drt")
        scat = _run(pK, part, layout, sched, path="edge", codec=None,
                    algorithm="drt", max_in_degree=None)
        assert _max_err(csr[0], scat[0]) < 1e-5, name


def test_edge_padding_columns_are_inert():
    pK, part, layout = _stack()
    topo = ring(K)
    edges = edge_stacks_from_topology(topo, ROUNDS)
    padded = EdgeStacks(
        jnp.pad(edges.src, ((0, 0), (0, 5))),
        jnp.pad(edges.dst, ((0, 0), (0, 5))),
        jnp.pad(edges.w, ((0, 0), (0, 5))),
    )
    C = jnp.asarray(topo.c_matrix(), jnp.float32)
    metro = jnp.asarray(topo.metropolis(), jnp.float32)
    outs = []
    for e in (edges, padded):
        outs.append(
            gather_consensus_rounds(
                part, pK, C, DRTConfig(), rounds=ROUNDS, algorithm="drt",
                metropolis=metro, layout=layout, path="edge", edges=e,
                max_in_degree=2,
            )[0]
        )
    assert _max_err(*outs) == 0.0


def test_churn_isolated_agent_keeps_its_iterate_on_the_edge_path():
    pK, part, layout = _stack()
    sched = _schedules()["churn"]
    # find a round where churn isolates at least one agent
    t_iso, k_iso = None, None
    for t in range(16):
        adj = np.asarray(sched.topology_at(t).adjacency, dtype=bool)
        np.fill_diagonal(adj, False)
        deg = adj.sum(1)
        if (deg == 0).any():
            t_iso, k_iso = t, int(np.argmax(deg == 0))
            break
    assert t_iso is not None, "churn schedule never isolated an agent"
    C, metro = sched.mixing_stacks(t_iso, 1)
    out = gather_consensus_rounds(
        part, pK, C, DRTConfig(), rounds=1, algorithm="drt",
        metropolis=metro, layout=layout, path="edge",
        edges=sched.edge_stacks(t_iso, 1),
        max_in_degree=sched.max_in_degree,
    )[0]
    for a, b in zip(jax.tree.leaves(pK), jax.tree.leaves(out)):
        np.testing.assert_allclose(a[k_iso], b[k_iso], rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# telemetry on the edge path
# ---------------------------------------------------------------------------


def test_edge_path_telemetry_matches_dense_disagreement():
    from repro.obs import ObsConfig

    pK, part, layout = _stack()
    sched = _schedules()["static_ring"]
    obs = ObsConfig()
    dense = _run(pK, part, layout, sched, path="slab", codec="bf16",
                 algorithm="drt", obs=obs)
    edge = _run(pK, part, layout, sched, path="edge", codec="bf16",
                algorithm="drt", obs=obs)
    md, me = dense[3], edge[3]
    np.testing.assert_allclose(
        np.asarray(md.disagreement), np.asarray(me.disagreement),
        rtol=1e-3, atol=1e-5,
    )
    assert float(jnp.min(me.wire_send_bytes)) > 0
    # ring: every agent receives from its 2 in-neighbours
    np.testing.assert_allclose(
        np.asarray(me.wire_recv_bytes), 2.0 * np.asarray(me.wire_send_bytes)
    )


# ---------------------------------------------------------------------------
# fused segment kernel: one launch per round
# ---------------------------------------------------------------------------


def test_edge_kernel_one_launch_per_round():
    from repro.utils.dispatch import count_pallas_launches

    pK, part, layout = _stack(K=4)
    topo = ring(4)
    C = jnp.asarray(topo.c_matrix(), jnp.float32)
    metro = jnp.asarray(topo.metropolis(), jnp.float32)
    edges = edge_stacks_from_topology(topo, ROUNDS)
    for codec in (None, "bf16"):
        n = count_pallas_launches(
            lambda pK, codec=codec: gather_consensus_rounds(
                part, pK, C, DRTConfig(), rounds=ROUNDS, algorithm="drt",
                metropolis=metro,
                codec=codec, rng=jax.random.key(0) if codec else None,
                layout=layout, path="edge", edges=edges, use_kernels=True,
            )[0],
            pK,
        )
        assert n == ROUNDS, (codec, n)


# ---------------------------------------------------------------------------
# wire-resident fused round (PR 9): in-kernel decode + CSR segment combine
# ---------------------------------------------------------------------------


def _run_kernels(pK, part, layout, sched, *, codec, algorithm,
                 use_kernels, rounds=ROUNDS):
    C, metro = sched.mixing_stacks(0, rounds)
    return gather_consensus_rounds(
        part, pK, C, DRTConfig(), rounds=rounds, algorithm=algorithm,
        metropolis=metro, codec=codec,
        rng=jax.random.key(7) if codec is not None else None,
        layout=layout, path="edge", edges=sched.edge_stacks(0, rounds),
        max_in_degree=sched.max_in_degree, use_kernels=use_kernels,
    )


@pytest.mark.parametrize("name", ["static_ring", "churn"])
@pytest.mark.parametrize("algorithm", ["drt", "classical"])
@pytest.mark.parametrize("codec", [None, "bf16", "int8", "topk:0.25"])
def test_wire_resident_kernel_matches_jnp_edge_path(name, algorithm, codec):
    """``slab_edge_encode_combine`` (in-kernel wire decode + sort-free CSR
    combine, interpret mode) vs the jnp CSR edge path, same rng: exact and
    top-k wires are bit-identical; bf16/int8 sit at 1-2 ulp (the decode
    values match bit for bit — separately compiled programs contract
    different FMA chains).  EF residual and mixing matrices ride along."""
    pK, part, layout = _stack()
    sched = _schedules()[name]
    ref = _run_kernels(pK, part, layout, sched, codec=codec,
                       algorithm=algorithm, use_kernels=False)
    ker = _run_kernels(pK, part, layout, sched, codec=codec,
                       algorithm=algorithm, use_kernels=True)
    assert _max_err(ref[0], ker[0]) < 1e-5, (name, algorithm, codec)
    assert float(jnp.max(jnp.abs(ref[1] - ker[1]))) < 1e-6
    if codec == "topk:0.25":
        assert _max_err(ref[2], ker[2]) == 0.0  # EF residual: jnp encode
    if codec in (None, "topk:0.25"):
        # f32 wire: the kernel reads the very same values the jnp path does
        assert _max_err(ref[0], ker[0]) == 0.0


def test_wire_resident_kernel_one_launch_per_round():
    """With CSR tables available every CODED round is one Pallas launch —
    the wire-resident kernel subsumes gather, decode, stats, mixing and
    combine (no decoded-slab round trip to re-read)."""
    from repro.utils.dispatch import count_pallas_launches

    pK, part, layout = _stack(K=4)
    topo = ring(4)
    C = jnp.asarray(topo.c_matrix(), jnp.float32)
    metro = jnp.asarray(topo.metropolis(), jnp.float32)
    edges = edge_stacks_from_topology(topo, ROUNDS)
    dmax = max_in_degree_from_topology(topo)
    for codec in (None, "bf16", "int8", "topk:0.25"):
        n = count_pallas_launches(
            lambda pK, codec=codec: gather_consensus_rounds(
                part, pK, C, DRTConfig(), rounds=ROUNDS, algorithm="drt",
                metropolis=metro,
                codec=codec, rng=jax.random.key(0) if codec else None,
                layout=layout, path="edge", edges=edges, use_kernels=True,
                max_in_degree=dmax,
            )[0],
            pK,
        )
        assert n == ROUNDS, (codec, n)


# ---------------------------------------------------------------------------
# dryrun --graph-stats cost ratios: hand-computed ring / ER values
# ---------------------------------------------------------------------------


def test_graph_stats_flop_and_byte_ratios_hand_computed():
    from repro.core.dynamic import StaticSchedule, schedule_graph_stats

    K64 = 64
    # ring: 2K directed edges -> FLOP ratio K^2 / 2K = K/2
    s = schedule_graph_stats(StaticSchedule(ring(K64)))
    assert s["dense_vs_edge_flop_ratio"] == pytest.approx(K64 / 2.0)
    # int8 wire (1 B/elem): dense 3 f32 passes = 12 B/elem vs edge
    # self + out f32 (8 B) + wire x2 phases (2 B) -> 12/10
    assert s["dense_vs_edge_byte_ratio"] == pytest.approx(1.2)

    er = make_topology("erdos_renyi", K64, p=0.1, seed=0)
    adj = np.asarray(er.adjacency, dtype=bool).copy()
    np.fill_diagonal(adj, False)
    e_directed = int(adj.sum())
    s_er = schedule_graph_stats(StaticSchedule(er))
    assert s_er["dense_vs_edge_flop_ratio"] == pytest.approx(
        K64 * K64 / e_directed
    )
    # bytes are graph-INDEPENDENT (the replicated wire streams whole per
    # phase whatever |E| is): ER and ring agree exactly, and the ratio
    # moves only with the wire width
    assert s_er["dense_vs_edge_byte_ratio"] == s["dense_vs_edge_byte_ratio"]
    s_bf16 = schedule_graph_stats(StaticSchedule(er), wire_itemsize=2)
    assert s_bf16["dense_vs_edge_byte_ratio"] == pytest.approx(1.0)
    s_f32 = schedule_graph_stats(StaticSchedule(er), wire_itemsize=4)
    assert s_f32["dense_vs_edge_byte_ratio"] == pytest.approx(0.75)


# ---------------------------------------------------------------------------
# in-kernel (masked-reduction) spelling of the edge-factor algebra
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("graph", ["ring", "erdos_renyi"])
def test_dense_edge_segments_match_scatter_spelling(graph):
    """The one-hot / masked-reduction spelling the compiled edge kernels run
    (``dense=True``) gives the scatter path's edge factors to a few f32 ulps
    (gathers, mins and maxes agree exactly; sums and fused elementwise
    chains may round in another order), at ring and ER in-degrees."""
    from repro.core.drt import DRTConfig, drt_edge_mixing
    from repro.core.dynamic import edge_stacks_from_topology, metropolis_edge_weights

    K64, L = 32, 5
    topo = (ring(K64) if graph == "ring"
            else make_topology("erdos_renyi", K64, p=0.15, seed=2))
    e = edge_stacks_from_topology(topo, 1)
    src, dst, w = e.src[0], e.dst[0], e.w[0]
    k1, k2 = jax.random.split(jax.random.key(4))
    d2 = jax.random.uniform(k1, (L, src.shape[0])) * 3.0
    n2 = jax.random.uniform(k2, (L, K64)) * 5.0 + 1.0
    for dense_fn in (
        lambda dense: drt_edge_mixing(d2, n2, src, dst, w, DRTConfig(), K64, dense),
        lambda dense: metropolis_edge_weights(src, dst, w, K64, dense),
    ):
        for a, b in zip(jax.jit(dense_fn, static_argnums=0)(False),
                        jax.jit(dense_fn, static_argnums=0)(True)):
            assert float(jnp.max(jnp.abs(a - b))) <= 1e-6  # factors are <= 1
