"""Mixture-of-Experts layers: GShard-style capacity dispatch
(:func:`moe_apply`, the ``"moe"`` layer kind) and the dropless expert-share
layer of DeepSeek-V3-style models (:func:`expert_share_apply`, ``"mla_moe"``).

Top-k routing with per-group capacity, dispatch/combine expressed as einsums
so the expert dimension shards cleanly under pjit (expert parallelism: the
``E`` axis carries a mesh axis; the (tokens x experts) contractions lower to
all-to-all / all-gather collectives chosen by SPMD).

The dispatch einsum moves bytes via the MXU — a known GShard-era overhead
(roughly 0.5-1x of true expert FLOPs at kimi-k2 settings).  The §Perf loop
measures it via the MODEL_FLOPS / HLO_FLOPs ratio; a scatter-based dispatch
is the recorded alternative.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.moe_gmm import ROW_TILE, moe_gmm
from repro.models.config import MoECfg
from repro.models.layers import dense_init

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

# -- expert-parallel sharding hints (set by the launcher) ---------------------
# Without explicit constraints GSPMD occasionally falls back to "involuntary
# full rematerialization" (replicating whole expert tensors) when resolving
# the dispatch einsums; pinning the expert axis fixes the all-to-all pattern.

_EP_MESH = None
_EP_AXIS = None


class expert_parallel_scope:
    def __init__(self, mesh, expert_axis: str | None):
        self.mesh, self.axis = mesh, expert_axis

    def __enter__(self):
        global _EP_MESH, _EP_AXIS
        self._prev = (_EP_MESH, _EP_AXIS)
        _EP_MESH, _EP_AXIS = self.mesh, self.axis
        return self

    def __exit__(self, *exc):
        global _EP_MESH, _EP_AXIS
        _EP_MESH, _EP_AXIS = self._prev


def _constrain(x, *axes):
    """Best-effort sharding constraint on the trailing len(axes) dims."""
    if _EP_MESH is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = [None] * (x.ndim - len(axes)) + [
        a if (a is None or a in _EP_MESH.axis_names) else None for a in axes
    ]
    try:
        return jax.lax.with_sharding_constraint(x, NamedSharding(_EP_MESH, P(*spec)))
    except Exception:
        return x


def moe_params(key, d_model: int, moe: MoECfg, dtype):
    ks = jax.random.split(key, 7)
    p = {
        "router": dense_init(ks[0], (d_model, moe.n_experts), dtype),
        "we_gate": dense_init(ks[1], (moe.n_experts, d_model, moe.d_ff_expert), dtype),
        "we_up": dense_init(ks[2], (moe.n_experts, d_model, moe.d_ff_expert), dtype),
        "we_down": dense_init(ks[3], (moe.n_experts, moe.d_ff_expert, d_model), dtype),
    }
    if moe.shared_d_ff:
        p["ws_gate"] = dense_init(ks[4], (d_model, moe.shared_d_ff), dtype)
        p["ws_up"] = dense_init(ks[5], (d_model, moe.shared_d_ff), dtype)
        p["ws_down"] = dense_init(ks[6], (moe.shared_d_ff, d_model), dtype)
    return p


def moe_apply(p, x, moe: MoECfg, compute_dtype):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar)."""
    B, S, d = x.shape
    T = B * S
    gs = min(moe.group_size, T)
    G = T // gs
    assert G * gs == T, f"tokens {T} not divisible by group size {gs}"
    E, k = moe.n_experts, moe.top_k
    cap = int(np.ceil(gs * k / E * moe.capacity_factor))
    cap = max(cap, k)

    xt = x.reshape(G, gs, d)
    logits = jnp.einsum(
        "gsd,de->gse", xt.astype(F32), p["router"].astype(F32)
    )  # router in f32
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # (G, gs, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    # expert-choice bookkeeping: position of each (token, slot) in its
    # expert's queue, computed with a cumulative sum over the group
    onehot = jax.nn.one_hot(gate_idx, E, dtype=F32)  # (G, gs, k, E)
    slot_mask = onehot.reshape(G, gs * k, E)
    pos = jnp.cumsum(slot_mask, axis=1) - 1.0  # (G, gs*k, E)
    keep = (pos < cap) & (slot_mask > 0)
    pos_tok = (pos * slot_mask).sum(-1).reshape(G, gs, k)  # queue position
    keep_tok = keep.any(-1).reshape(G, gs, k)

    # dispatch (G, gs, E, cap) and combine weights — accumulated one routing
    # slot at a time so no (G, gs, k, E, cap) intermediate is materialized
    pos_i = pos_tok.astype(jnp.int32)
    disp = jnp.zeros((G, gs, E, cap), compute_dtype)
    comb = jnp.zeros((G, gs, E, cap), F32)
    for slot in range(k):
        oe = jax.nn.one_hot(gate_idx[..., slot], E, dtype=F32)  # (G, gs, E)
        oc = jax.nn.one_hot(pos_i[..., slot], cap, dtype=F32)  # (G, gs, cap)
        kp = keep_tok[..., slot].astype(F32)  # (G, gs)
        term = oe[..., :, None] * oc[..., None, :] * kp[..., None, None]
        disp = disp + term.astype(compute_dtype)
        comb = comb + term * gate_vals[..., slot].astype(F32)[..., None, None]

    expert_in = jnp.einsum(
        "gsec,gsd->gecd", disp, xt.astype(compute_dtype)
    )  # (G, E, cap, d)
    expert_in = _constrain(expert_in, _EP_AXIS, None, None)  # E sharded (EP)
    g = jnp.einsum("gecd,edf->gecf", expert_in, p["we_gate"].astype(compute_dtype))
    u = jnp.einsum("gecd,edf->gecf", expert_in, p["we_up"].astype(compute_dtype))
    h = jax.nn.silu(g) * u
    h = _constrain(h, _EP_AXIS, None, "model")
    expert_out = jnp.einsum("gecf,efd->gecd", h, p["we_down"].astype(compute_dtype))
    expert_out = _constrain(expert_out, _EP_AXIS, None, None)
    out = jnp.einsum("gsec,gecd->gsd", comb.astype(compute_dtype), expert_out)
    out = out.reshape(B, S, d)

    if moe.shared_d_ff:
        sg = jnp.einsum("bsd,df->bsf", x, p["ws_gate"].astype(compute_dtype))
        su = jnp.einsum("bsd,df->bsf", x, p["ws_up"].astype(compute_dtype))
        out = out + jnp.einsum(
            "bsf,fd->bsd", jax.nn.silu(sg) * su, p["ws_down"].astype(compute_dtype)
        )

    # Switch-style load-balance auxiliary loss
    frac_tokens = jnp.mean(
        jax.nn.one_hot(gate_idx[..., 0], E, dtype=F32), axis=(0, 1)
    )  # top-1 assignment fraction
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac_tokens * frac_probs) * moe.aux_loss_weight
    return out, aux


# ---------------------------------------------------------------------------
# the expert-share layer (DeepSeek-V3 routing, dropless, over held experts)
# ---------------------------------------------------------------------------
#
# A deployment spreads each layer's routed experts over the chips that share
# it; this layer is told which experts it holds, [offset, offset + n_held),
# routes every token over all ``n_experts`` and computes only its own
# experts' part of the output, for every token slot routed to them: no
# capacity, nothing dropped.  The parts of all the shares, plus the shared
# experts and the balance loss (which every share computes alike, so they
# are counted once), make the whole layer.  On one chip the layer runs
# without its exchange: its own tokens reach its experts, nobody else's.


def expert_share_params(key, d_model: int, moe: MoECfg, dtype):
    ks = jax.random.split(key, 7)
    G, f = moe.held, moe.d_ff_expert
    p = {
        "router": dense_init(ks[0], (d_model, moe.n_experts), dtype),
        "we_gate": dense_init(ks[1], (G, d_model, f), dtype),
        "we_up": dense_init(ks[2], (G, d_model, f), dtype),
        "we_down": dense_init(ks[3], (G, f, d_model), dtype),
    }
    if moe.shared_d_ff:
        p["ws_gate"] = dense_init(ks[4], (d_model, moe.shared_d_ff), dtype)
        p["ws_up"] = dense_init(ks[5], (d_model, moe.shared_d_ff), dtype)
        p["ws_down"] = dense_init(ks[6], (moe.shared_d_ff, d_model), dtype)
    return p


def route(x, router, moe: MoECfg):
    """DeepSeek-V3's router: sigmoid scores over all experts in float32, the
    top ``top_k`` and their weights, normalised over the top ``top_k`` and
    times ``routed_scale``.  Returns ``(scores (..., E), gate (..., k), idx
    (..., k))``."""
    logits = jnp.einsum("...d,de->...e", x.astype(F32), router.astype(F32), precision=HIGHEST)
    scores = jax.nn.sigmoid(logits)
    top, idx = jax.lax.top_k(scores, moe.top_k)
    gate = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) * moe.routed_scale
    return scores, gate, idx


def balance_loss(scores, idx, moe: MoECfg):
    """DeepSeek-V3's sequence-wise balance loss (eqs. 17-20), averaged over
    the sequences: ``alpha * sum_i f_i P_i`` with ``f_i`` the share of the
    sequence's slots routed to expert i times ``E / k`` and ``P_i`` its mean
    normalised score.  scores: (B, S, E), idx: (B, S, k)."""
    E, k = moe.n_experts, moe.top_k
    S = scores.shape[1]
    chosen = jnp.sum(jax.nn.one_hot(idx, E, dtype=F32), axis=(1, 2))  # (B, E)
    f = chosen * (E / (k * S))
    P = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=1)
    return moe.aux_loss_weight * jnp.mean(jnp.sum(f * P, axis=-1))


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _dispatch(x, order, inv, held, k: int, rows: int):
    """The token of each slot in ``order``, as ``rows`` rows (zeros past the
    ``T*k`` slots).  Its VJP gathers each slot's row back and sums a
    token's held slots: a gather, where autodiff would scatter-add."""
    out = x[order // k]
    return jnp.pad(out, ((0, rows - out.shape[0]), (0, 0)))


def _dispatch_fwd(x, order, inv, held, k, rows):
    return _dispatch(x, order, inv, held, k, rows), (inv, held)


def _dispatch_bwd(k, rows, res, g):
    inv, held = res
    g = jnp.where(held[:, None], g[inv], 0)  # rows of unheld slots are undefined
    return g.reshape(-1, k, g.shape[-1]).sum(axis=1), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _collect(y, inv, order, rows: int):
    """Each slot's row of ``y``; its VJP is the inverse permutation."""
    return y[inv]


def _collect_fwd(y, inv, order, rows):
    return y[inv], order


def _collect_bwd(rows, order, g):
    g = g[order]
    return jnp.pad(g, ((0, rows - g.shape[0]), (0, 0))), None, None


_collect.defvjp(_collect_fwd, _collect_bwd)


def held_experts_apply(p, x, gate, idx, moe: MoECfg, compute_dtype):
    """The held experts' part of the output: x (T, d), gate/idx (T, k) ->
    (out (T, d), group sizes (n_held,)).

    Each slot routed to a held expert becomes one row of a static buffer of
    ``T*k`` rows (every slot could land here), sorted by expert; the held
    experts' SiLU-gated FFNs run as grouped matrix products over the routed
    rows (``kernels.moe_gmm``), and each slot's row comes back weighted by
    its gate.  Rows past the routed ones are never computed and are selected
    away, forward and backward."""
    cd = compute_dtype
    T, d = x.shape
    k, G, f = moe.top_k, moe.held, moe.d_ff_expert
    rows = -(-T * k // ROW_TILE) * ROW_TILE
    with jax.named_scope("moe_dispatch"):
        local = idx.reshape(T * k) - moe.held_offset
        held = (local >= 0) & (local < G)
        key = jnp.where(held, local, G)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inv = jnp.zeros_like(order).at[order].set(jnp.arange(T * k, dtype=jnp.int32))
        sizes = jnp.sum(jax.nn.one_hot(key, G, dtype=jnp.int32), axis=0)
        xs = _dispatch(x.astype(cd), order, inv, held, k, rows)
    with jax.named_scope("moe_experts"):
        w_in = jnp.concatenate([p["we_gate"], p["we_up"]], axis=-1).astype(cd)
        h = moe_gmm(xs, w_in, sizes)
        y = moe_gmm(jax.nn.silu(h[:, :f]) * h[:, f:], p["we_down"].astype(cd), sizes)
    with jax.named_scope("moe_combine"):
        y = jnp.where(held[:, None], _collect(y, inv, order, rows), 0).reshape(T, k, d)
        out = jnp.einsum("tk,tkd->td", gate.astype(cd), y, preferred_element_type=F32)
    return out.astype(cd), sizes


def shared_experts_apply(p, x, compute_dtype):
    cd = compute_dtype
    with jax.named_scope("moe_shared"):
        g = jnp.einsum("...d,df->...f", x, p["ws_gate"].astype(cd))
        u = jnp.einsum("...d,df->...f", x, p["ws_up"].astype(cd))
        return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, p["ws_down"].astype(cd))


def expert_share_apply(p, x, moe: MoECfg, compute_dtype):
    """x: (B, S, d) -> (out (B, S, d), balance loss, stats).

    ``stats``: ``routed_slots``, the token slots routed to held experts, and
    ``held_imbalance``, the largest held expert's rows over their mean."""
    B, S, d = x.shape
    with jax.named_scope("moe_route"):
        scores, gate, idx = route(x, p["router"], moe)
        aux = balance_loss(scores, idx, moe)
    out, sizes = held_experts_apply(
        p, x.reshape(B * S, d), gate.reshape(B * S, -1), idx.reshape(B * S, -1), moe,
        compute_dtype,
    )
    out = out.reshape(B, S, d)
    if moe.shared_d_ff:
        out = out + shared_experts_apply(p, x, compute_dtype)
    sizes = sizes.astype(F32)
    stats = {
        "routed_slots": jnp.sum(sizes),
        "held_imbalance": jnp.max(sizes) / jnp.maximum(jnp.mean(sizes), 1e-9),
    }
    return out, aux, stats
