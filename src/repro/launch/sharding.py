"""Sharding rules: parameter / optimizer-state / batch PartitionSpecs.

Rules are keyed on leaf names (the param dicts use stable names across all
families).  Divisibility fallbacks are applied per architecture:

* attention heads shard over ``model`` when n_heads % 16 == 0, otherwise the
  d_model (contracting) dimension shards instead (whisper's 20 heads,
  hymba's 25, llava's 56, llama4's 40);
* GQA kv projections (n_kv_heads=8 < 16 everywhere) always d-shard;
* vocab shards over ``model`` unless indivisible (hymba's 32001, whisper's
  51866), in which case the embedding width shards;
* MoE expert tensors shard E over ``cfg.expert_axis`` — ``model`` for K=16
  archs, ``data`` (true expert parallelism, agent axis replicated) for the
  memory-gated giants (llama4, kimi) — with the expert ffn dim over ``model``
  in the latter case;
* every leaf under a ``*_blocks`` key gets a leading ``None`` for the scan
  axis; agent-stacked trees get ``data`` on the leading K axis when K equals
  the data-axis size, ``None`` (replicated) otherwise.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.dynamic import EdgeStacks
from repro.launch.mesh import batch_axes, mesh_axis_sizes
from repro.models.config import ModelConfig

PyTree = Any


def _leaf_spec(path_keys: tuple[str, ...], ndim: int, cfg: ModelConfig, axes: dict[str, int]) -> P:
    """Spec for a single un-stacked, un-agented leaf."""
    name = path_keys[-1]
    msize = axes.get("model", 1)
    a = cfg.attn
    head_ok = a is not None and a.n_heads % msize == 0
    kv_ok = a is not None and a.n_kv_heads % msize == 0
    vocab_ok = cfg.vocab % msize == 0
    e_ax = cfg.expert_axis

    if name == "tok":  # (V, d)
        return P("model", None) if vocab_ok else P(None, "model")
    if name == "enc_pos":
        return P(None, None)
    if name == "wq":  # (d, H, hd)
        return P(None, "model", None) if head_ok else P("model", None, None)
    if name in ("wk", "wv"):  # (d, Hkv, hd)
        return P(None, "model", None) if kv_ok else P("model", None, None)
    if name == "wo":  # (H, hd, d)
        return P("model", None, None) if head_ok else P(None, None, "model")
    if name in ("w_gate", "w_up", "w_in", "ws_gate", "ws_up", "w1"):  # (d, ff)
        return P(None, "model")
    if name in ("w_down", "w_out", "ws_down", "w2"):  # (ff, d)
        return P("model", None)
    if name == "router":  # (d, E) — small; replicate
        return P(None, None)
    if name in ("we_gate", "we_up"):  # (E, d, ffe)
        return P("model", None, None) if e_ax == "model" else P("data", None, "model")
    if name == "we_down":  # (E, ffe, d)
        return P("model", None, None) if e_ax == "model" else P("data", "model", None)
    if name == "in_proj":  # (d, 2*di)
        return P(None, "model")
    if name == "conv_w":  # (d_conv, di)
        return P(None, "model")
    if name in ("conv_b", "dt_bias", "D"):  # (di,)
        return P("model")
    if name in ("x_proj", "A_log", "out_proj"):  # (di, ·)
        return P("model", None)
    if name == "dt_proj":  # (dt_rank, di)
        return P(None, "model")
    if name == "w" and "lm_head" in path_keys:  # (d, V)
        return P(None, "model") if vocab_ok else P("model", None)
    # norms, biases, betas, scalars, resnet leaves: replicated
    return P(*([None] * ndim))


def _path_names(path) -> tuple[str, ...]:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(f"#{p.idx}")
    return tuple(out)


def param_pspecs(
    cfg: ModelConfig, params_abstract: PyTree, mesh, *, with_agents: bool
) -> PyTree:
    """PartitionSpec tree matching ``params_abstract`` (leaves: ShapeDtypeStruct).

    ``with_agents``: leaves carry a leading K axis (decentralized training).
    """
    axes = mesh_axis_sizes(mesh)
    dsize = axes.get("data", 1)
    agent_axis = (
        "data" if (with_agents and cfg.num_agents == dsize) else None
    )

    def spec_for(path, leaf):
        names = _path_names(path)
        ndim = len(leaf.shape)
        extra = 0
        stacked = any(n.endswith("_blocks") for n in names)
        if stacked:
            extra += 1
        if with_agents:
            extra += 1
        base = _leaf_spec(names, ndim - extra, cfg, axes)
        parts = list(base)
        if stacked:
            parts = [None] + parts
        if with_agents:
            parts = [agent_axis] + parts
        return P(*parts)

    return jax.tree_util.tree_map_with_path(spec_for, params_abstract)


# ---------------------------------------------------------------------------
# batch / cache / activation specs
# ---------------------------------------------------------------------------


def train_batch_pspecs(cfg: ModelConfig, batch_abstract: PyTree, mesh) -> PyTree:
    """Per-agent batches: leading (K, B_agent, ...).  K -> data axis when
    K == |data| (else replicated, batch over data); B -> pod (and data when K
    is replicated)."""
    axes = mesh_axis_sizes(mesh)
    dsize = axes.get("data", 1)
    has_pod = "pod" in axes
    if cfg.num_agents == dsize:
        k_ax, b_ax = "data", ("pod" if has_pod else None)
    else:
        k_ax, b_ax = None, (("data", "pod") if has_pod else "data")

    def spec_for(path, leaf):
        parts = [k_ax, b_ax] + [None] * (len(leaf.shape) - 2)
        return P(*parts)

    return jax.tree_util.tree_map_with_path(spec_for, batch_abstract)


def serve_batch_pspecs(batch_abstract: PyTree, mesh) -> PyTree:
    """Serving batches (B, ...): B over ('pod','data') when divisible,
    replicated otherwise (long_500k has B=1)."""
    b_ax = batch_axes(mesh)
    axes = mesh_axis_sizes(mesh)
    n_b = 1
    for a in b_ax:
        n_b *= axes[a]

    def spec_for(path, leaf):
        if not leaf.shape:
            return P()
        lead = b_ax if leaf.shape[0] % n_b == 0 else None
        return P(lead, *([None] * (len(leaf.shape) - 1)))

    return jax.tree_util.tree_map_with_path(spec_for, batch_abstract)


def cache_pspecs(cfg: ModelConfig, caches_abstract, mesh, batch_size: int):
    """Decode caches.  KV tensors (B, S, Hkv, hd): B over data when it divides,
    S over model (sequence-parallel decode: softmax stats psum over model);
    for B == 1 (long_500k) S shards over BOTH (data, model).  Mamba states
    (B, d_conv-1, di)/(B, di, ds): di over model (+ data when B == 1).
    Every axis assignment checks divisibility and degrades to replication
    (whisper's 1500-frame cross cache, hymba's di=3200)."""
    axes = mesh_axis_sizes(mesh)
    dsize = axes.get("data", 1)
    msize = axes.get("model", 1)
    b_shardable = batch_size % dsize == 0

    def fit(dim: int, *cands):
        """First candidate axis-combo that divides dim."""
        for c in cands:
            n = 1
            for a in (c if isinstance(c, tuple) else (c,)):
                n *= axes[a]
            if dim % n == 0:
                return c
        return None

    def spec_for(path, leaf):
        names = _path_names(path)
        name = names[-1] if names else ""
        shape = leaf.shape
        if name in ("k", "v", "ck", "cv") and len(shape) == 4:
            if b_shardable:
                return P("data", fit(shape[1], "model"), None, None)
            return P(None, fit(shape[1], ("data", "model"), "model", "data"), None, None)
        if name == "conv" and len(shape) == 3:  # (B, d_conv-1, di)
            di_ax = fit(shape[2], *((("data", "model"), "model") if not b_shardable else ("model",)))
            return P(None, None, di_ax)
        if name == "ssm" and len(shape) == 3:  # (B, di, ds)
            di_ax = fit(shape[1], *((("data", "model"), "model") if not b_shardable else ("model",)))
            return P(None, di_ax, None)
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(spec_for, caches_abstract)


# ---------------------------------------------------------------------------
# consensus (agent-axis) specs — the edge path at K = 64 / 256
# ---------------------------------------------------------------------------


def consensus_slab_pspec(mesh, num_agents: int) -> P:
    """Spec for a ``(K, D)`` flat consensus slab: the agent axis shards over
    ``data`` whenever K divides by it (K = 64 on an 8-way data mesh puts 8
    agents per shard), replicating otherwise.  D stays unsharded — the edge
    combine gathers whole rows by source agent."""
    axes = mesh_axis_sizes(mesh)
    dsize = axes.get("data", 1)
    k_ax = "data" if num_agents % dsize == 0 else None
    return P(k_ax, None)


def edge_stack_pspecs(mesh, e_max: int) -> EdgeStacks:
    """Specs for ``EdgeStacks`` leaves ``(rounds, E_max)``: the edge axis
    shards over ``data`` when E_max divides by it.  Because the tables are
    (dst, src)-sorted, contiguous edge shards are destination-contiguous, so
    on regular graphs (ring, torus, hypercube) each shard's scatter targets
    land on the agents the slab spec places on the same devices."""
    axes = mesh_axis_sizes(mesh)
    dsize = axes.get("data", 1)
    e_ax = "data" if e_max % dsize == 0 else None
    spec = P(None, e_ax)
    return EdgeStacks(src=spec, dst=spec, w=spec)


def shard_consensus_inputs(mesh, psi_K, edges: "EdgeStacks | None" = None):
    """Place a ``(K, D)`` slab (and optionally its edge stacks) on ``mesh``
    with the consensus layout.  Returns ``(psi_K, edges)`` device_put with
    :func:`consensus_slab_pspec` / :func:`edge_stack_pspecs`."""
    slab = jax.device_put(
        psi_K, NamedSharding(mesh, consensus_slab_pspec(mesh, psi_K.shape[0]))
    )
    if edges is None:
        return slab, None
    especs = edge_stack_pspecs(mesh, edges.src.shape[-1])
    placed = EdgeStacks(
        *(
            jax.device_put(x, NamedSharding(mesh, s))
            for x, s in zip(edges, especs)
        )
    )
    return slab, placed


def edge_round_shard_specs(mesh, num_agents: int) -> dict:
    """shard_map PartitionSpecs for ONE wire-resident edge round
    (``repro.kernels.slab_edge_encode_combine``) on the data mesh.

    The kernel is destination-sharded: each shard owns a contiguous run of
    destination agents — its rows of the f32 self slab, the combined output,
    and the CSR tables (``csr_from_edges`` rows are per-destination).  The
    compact WIRE is replicated: a destination's in-neighbours can live on
    any shard, but the wire is the codec-compressed form, so replicating it
    moves rho = wire/f32 of a slab instead of all-gathering f32 rows.  The
    edge list is replicated too — the per-edge stats/mixing factors are
    D-free global algebra every shard recomputes redundantly (cheaper than
    a cross-shard reduce at these sizes), so ``A_self``/``A_e`` come back
    replicated.  Agent axis falls back to replication when K doesn't divide
    the data axis.
    """
    axes = mesh_axis_sizes(mesh)
    dsize = axes.get("data", 1)
    k_ax = "data" if num_agents % dsize == 0 else None
    return {
        "self_slab": P(k_ax, None),  # (K, D) f32 — local destination rows
        "csr": P(k_ax, None),  # nbr/pos/valid (K, Dmax) — rows follow dst
        "wire": P(None, None),  # compact wire (K, ...) — replicated
        "edges": P(None),  # (E,) src/dst/w — replicated (global stats)
        "out": P(k_ax, None),  # combined (K, D)
        "A": P(None, None),  # A_self (L, K) / A_e (L, E) — replicated
    }


def shard_edge_round(
    mesh,
    block_layer,
    self_slab,
    wire_operands: tuple,
    src,
    dst,
    w,
    nbr,
    pos,
    valid,
    **kernel_kw,
):
    """Run ONE ``slab_edge_encode_combine`` launch per data shard over the
    destination-sharded slab (specs from :func:`edge_round_shard_specs`).

    Each shard passes its ``shard_index * K_local`` as ``dst_base`` so the
    kernel selects its own columns of the (replicated) ``A_self``.  Returns
    ``(combined (K, D), A_self (L, K), A_e (L, E))`` exactly like the
    unsharded kernel; when K doesn't divide the data axis (or the mesh has
    no data axis) the kernel simply runs replicated.
    """
    from jax import shard_map

    from repro.kernels import slab_edge_encode_combine

    K = self_slab.shape[0]
    specs = edge_round_shard_specs(mesh, K)
    k_ax = specs["self_slab"][0]
    if k_ax is None:
        return slab_edge_encode_combine(
            block_layer, self_slab, wire_operands, src, dst, w,
            nbr, pos, valid, **kernel_kw,
        )
    dsize = mesh_axis_sizes(mesh)[k_ax]
    Kl = K // dsize

    def body(bl, self_l, wires, src, dst, w, nbr_l, pos_l, valid_l):
        base = jax.lax.axis_index(k_ax) * Kl
        return slab_edge_encode_combine(
            bl, self_l, wires, src, dst, w,
            nbr_l, pos_l, valid_l, base, **kernel_kw,
        )

    wire_specs = tuple(P(*([None] * x.ndim)) for x in wire_operands)
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(None), specs["self_slab"], wire_specs,
            specs["edges"], specs["edges"], specs["edges"],
            specs["csr"], specs["csr"], specs["csr"],
        ),
        out_specs=(specs["out"], specs["A"], specs["A"]),
        # the A outputs are recomputed identically on every shard; shard_map
        # can't prove that, so replication checking is off
        check_vma=False,
    )(block_layer, self_slab, wire_operands, src, dst, w, nbr, pos, valid)


def to_named(mesh, pspec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        pspec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
