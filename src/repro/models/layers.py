"""Shared neural-network layers (pure functions over explicit param dicts).

Conventions
-----------
* activations: (B, S, d) unless stated; attention heads (B, S, H, hd).
* params are plain nested dicts of jnp arrays; init functions are pure
  (usable under ``jax.eval_shape`` for the allocation-free dry-run).
* ``compute_dtype`` (usually bf16) applies to activations/matmuls; norms,
  softmax and rope run in f32.
* attention is *flash-style* (never materializes the (S, S) score matrix):
  causal self-attention runs over square blocks below the diagonal with a
  running max/denominator, and its custom VJP keeps only the output and the
  log-sum-exp, recomputing the blocks (FlashAttention-2); other full
  attention scans over KV chunks; sliding-window attention scans over Q
  chunks and dynamic-slices only the in-window KV span — its FLOPs scale
  with S x window, not S^2.  The value head width may differ from the
  query/key width (latent attention: 192 and 128).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

# ---------------------------------------------------------------------------
# unroll mode (dry-run cost analysis)
# ---------------------------------------------------------------------------
# XLA's cost_analysis counts while-loop bodies ONCE (verified: a 10-step scan
# reports 1/10th of the executed FLOPs).  For the roofline dry-run we unroll
# every sequential loop (layer scans + flash/mamba chunk scans) into python
# loops so the compiled HLO carries the exact FLOP/byte counts.  Runtime
# training keeps scans (compile-time/memory efficiency).

_UNROLL_INNER = False


def set_unroll_inner(value: bool) -> None:
    global _UNROLL_INNER
    _UNROLL_INNER = bool(value)


def unroll_inner() -> bool:
    return _UNROLL_INNER


class unroll_scope:
    """Context manager: unroll inner loops (dry-run cost pass)."""

    def __init__(self, value: bool = True):
        self.value = value

    def __enter__(self):
        self.prev = _UNROLL_INNER
        set_unroll_inner(self.value)

    def __exit__(self, *exc):
        set_unroll_inner(self.prev)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    if scale is None:
        scale = 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, F32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps: float = 1e-6):
    x32 = x.astype(F32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(F32))
    return out.astype(x.dtype)


def layer_norm(x, w, b, eps: float = 1e-5):
    x32 = x.astype(F32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mean) * jax.lax.rsqrt(var + eps) * w.astype(F32) + b.astype(F32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float = 10000.0):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=F32) / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)  # (hd/2,)
    angles = positions.astype(F32)[..., :, None, None] * freqs  # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x32 = x.astype(F32)
    x1, x2 = x32[..., : hd // 2], x32[..., hd // 2 :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# flash attention (pure jnp; Pallas kernel in repro.kernels.flash_attention)
# ---------------------------------------------------------------------------


def _gqa_expand(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    kv_chunk: int = 1024,
    q_chunk: int = 512,
):
    """Chunked attention.  q: (B,Sq,H,hd), k: (B,Skv,Hkv,hd), v:
    (B,Skv,Hkv,dv); returns (B,Sq,H,dv).

    ``window``: sliding-window size (keys in (i-window, i] attend); None =
    full causal.  ``q_offset``: absolute position of q[0] (for decode /
    chunked prefill).  Softmax statistics in f32.  Causal self-attention
    over a whole sequence goes by blocks of ``q_chunk`` (:func:`causal_attention`).
    """
    B, Sq, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    n_rep = H // Hkv
    k = _gqa_expand(k, n_rep)
    v = _gqa_expand(v, n_rep)
    scale = 1.0 / np.sqrt(hd)

    if window is not None and Sq > 1:
        return _windowed_attention(q, k, v, window, q_offset, q_chunk, scale)
    if causal and window is None and q_offset == 0 and Sq == Skv and Sq > 1 and not _UNROLL_INNER:
        return causal_attention(q, k, v, block=min(q_chunk, Sq))

    kv_chunk = min(kv_chunk, Skv)
    n_kv = -(-Skv // kv_chunk)
    pad_kv = n_kv * kv_chunk - Skv
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))

    q32 = (q.astype(F32) * scale).transpose(0, 2, 1, 3)  # (B,H,Sq,hd)
    kT = k.transpose(0, 2, 3, 1)  # (B,H,hd,Skv_p)
    vT = v.transpose(0, 2, 1, 3)  # (B,H,Skv_p,hd)
    q_pos = q_offset + jnp.arange(Sq)

    def body(carry, idx):
        m, l, acc = carry
        ks = idx * kv_chunk
        k_blk = jax.lax.dynamic_slice_in_dim(kT, ks, kv_chunk, axis=3)
        v_blk = jax.lax.dynamic_slice_in_dim(vT, ks, kv_chunk, axis=2)
        s = jnp.einsum(
            "bhqd,bhdk->bhqk", q32, k_blk.astype(F32), preferred_element_type=F32
        )
        kv_pos = ks + jnp.arange(kv_chunk)
        mask = kv_pos[None, :] <= q_pos[:, None] if causal else jnp.ones(
            (Sq, kv_chunk), bool
        )
        if window is not None:
            mask = mask & (kv_pos[None, :] > q_pos[:, None] - window)
        mask = mask & (kv_pos[None, :] < Skv)
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk.astype(F32), preferred_element_type=F32
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), -1e30, F32)
    l0 = jnp.zeros((B, H, Sq), F32)
    acc0 = jnp.zeros((B, H, Sq, dv), F32)
    if _UNROLL_INNER:
        carry = (m0, l0, acc0)
        for idx in range(n_kv):
            carry, _ = body(carry, jnp.asarray(idx))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), jnp.arange(n_kv))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _windowed_attention(q, k, v, window, q_offset, q_chunk, scale):
    """Sliding-window attention: per Q chunk, attend only the in-window KV
    span (length window + q_chunk), sliced dynamically.  FLOPs ~ S * window.
    Assumes self-attention layout (Skv == Sq span, q_offset aligns them)."""
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    q_chunk = min(q_chunk, Sq)
    n_q = -(-Sq // q_chunk)
    pad_q = n_q * q_chunk - Sq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    span = window + q_chunk  # kv positions that can be seen by this q chunk
    # pad kv on the left by `window` (slice start never negative) and on the
    # right so the LAST chunk's slice fits without dynamic_slice clamping
    # (clamping would silently shift the window for ragged Sq)
    right = max(0, (n_q - 1) * q_chunk + span - (window + Skv))
    k_pad = jnp.pad(k, ((0, 0), (window, right), (0, 0), (0, 0)))
    v_pad = jnp.pad(v, ((0, 0), (window, right), (0, 0), (0, 0)))

    def one_chunk(qi):
        qs = qi * q_chunk
        q_blk = jax.lax.dynamic_slice_in_dim(q, qs, q_chunk, axis=1)
        # absolute kv start of the span: (qs + q_offset) - window, shifted by
        # the left pad of `window` -> slice at qs + q_offset ... within k_pad
        # k_pad index j corresponds to absolute kv position j - window.
        ks = qs  # + q_offset - window + window (self-attention, q_offset into kv)
        k_blk = jax.lax.dynamic_slice_in_dim(k_pad, ks, span, axis=1)
        v_blk = jax.lax.dynamic_slice_in_dim(v_pad, ks, span, axis=1)
        s = jnp.einsum(
            "bqhd,bkhd->bhqk",
            q_blk.astype(F32) * scale,
            k_blk.astype(F32),
            preferred_element_type=F32,
        )
        q_pos = qs + jnp.arange(q_chunk)  # position within this seq
        kv_pos = ks + jnp.arange(span) - window  # absolute kv position
        mask = (kv_pos[None, :] <= q_pos[:, None]) & (
            kv_pos[None, :] > q_pos[:, None] - window
        )
        mask = mask & (kv_pos[None, :] >= 0) & (kv_pos[None, :] < Skv)
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk.astype(F32), preferred_element_type=F32)
        return o

    if _UNROLL_INNER:
        outs = jnp.stack([one_chunk(jnp.asarray(i)) for i in range(n_q)])
    else:
        outs = jax.lax.map(one_chunk, jnp.arange(n_q))  # (n_q, B, q_chunk, H, hd)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, n_q * q_chunk, H, v.shape[-1])
    return out[:, :Sq].astype(q.dtype)


def causal_attention(q, k, v, *, block: int = 512):
    """Causal self-attention by square blocks on and below the diagonal.
    q/k: (B,S,H,hd), v: (B,S,H,dv) -> (B,S,H,dv), scale 1/sqrt(hd).

    A row of query blocks runs over the key blocks up to its own, so the
    blocks above the diagonal cost nothing.  The custom VJP keeps the inputs,
    the output and each row's log-sum-exp and recomputes the blocks; no
    (S, S) array exists in either direction.  Products take the operands'
    dtype with f32 accumulation."""
    S = q.shape[1]
    pad = -S % block
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0))) for t in (q, k, v))
    qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # (B,H,Sp,·)
    out = _causal_blocks(qt, kt, vt, block)
    return out.transpose(0, 2, 1, 3)[:, :S]


def _block(x, i, block):
    return jax.lax.dynamic_slice_in_dim(x, i * block, block, axis=2)


def _block_scores(q_i, k_j, i, j, block):
    """Scaled scores of query block i against key block j, masked above
    the diagonal."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q_i, k_j, preferred_element_type=F32)
    s = s * (1.0 / np.sqrt(q_i.shape[-1]))
    row = i * block + jnp.arange(block)[:, None]
    col = j * block + jnp.arange(block)[None, :]
    return jnp.where(col <= row, s, -1e30)


def _causal_forward(q, k, v, block):
    B, H, Sp, _ = q.shape
    dv = v.shape[-1]

    def row(i):
        q_i = _block(q, i, block)

        def step(j, carry):
            m, l, acc = carry
            s = _block_scores(q_i, _block(k, j, block), i, j, block)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(v.dtype), _block(v, j, block),
                preferred_element_type=F32,
            )
            return m_new, l * corr + jnp.sum(p, axis=-1), acc

        init = (
            jnp.full((B, H, block), -1e30, F32),
            jnp.zeros((B, H, block), F32),
            jnp.zeros((B, H, block, dv), F32),
        )
        m, l, acc = jax.lax.fori_loop(0, i + 1, step, init)
        return (acc / l[..., None]).astype(q.dtype), m + jnp.log(l)

    o, lse = jax.lax.map(row, jnp.arange(Sp // block))  # (n, B, H, block, ·)
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, Sp, dv)
    return o, jnp.moveaxis(lse, 0, 2).reshape(B, H, Sp)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _causal_blocks(q, k, v, block):
    return _causal_forward(q, k, v, block)[0]


def _causal_blocks_fwd(q, k, v, block):
    o, lse = _causal_forward(q, k, v, block)
    return o, (q, k, v, o, lse)


def _causal_blocks_bwd(block, res, do):
    q, k, v, o, lse = res
    n = q.shape[2] // block
    scale = 1.0 / np.sqrt(q.shape[-1])
    delta = jnp.sum(do.astype(F32) * o.astype(F32), axis=-1)  # (B,H,Sp)

    def probs(i, j):
        """p and dS of block (i, j)."""
        s = _block_scores(_block(q, i, block), _block(k, j, block), i, j, block)
        p = jnp.exp(s - _block(lse[..., None], i, block))
        dp = jnp.einsum(
            "bhqd,bhkd->bhqk", _block(do, i, block), _block(v, j, block),
            preferred_element_type=F32,
        )
        return p, p * (dp - _block(delta[..., None], i, block))

    def dq_row(i):
        def step(j, dq):
            _, ds = probs(i, j)
            return dq + jnp.einsum(
                "bhqk,bhkd->bhqd", ds.astype(k.dtype), _block(k, j, block),
                preferred_element_type=F32,
            )

        dq0 = jnp.zeros(q.shape[:2] + (block, q.shape[-1]), F32)
        return jax.lax.fori_loop(0, i + 1, step, dq0) * scale

    def dkv_col(j):
        def step(i, carry):
            dk, dv = carry
            p, ds = probs(i, j)
            dv = dv + jnp.einsum(
                "bhqk,bhqd->bhkd", p.astype(do.dtype), _block(do, i, block),
                preferred_element_type=F32,
            )
            dk = dk + jnp.einsum(
                "bhqk,bhqd->bhkd", ds.astype(q.dtype), _block(q, i, block),
                preferred_element_type=F32,
            )
            return dk, dv

        zeros = lambda t: jnp.zeros(t.shape[:2] + (block, t.shape[-1]), F32)
        dk, dv = jax.lax.fori_loop(j, n, step, (zeros(k), zeros(v)))
        return dk * scale, dv

    def join(blocks, like):
        return jnp.moveaxis(blocks, 0, 2).reshape(like.shape).astype(like.dtype)

    dq = join(jax.lax.map(dq_row, jnp.arange(n)), q)
    dk, dv = jax.lax.map(dkv_col, jnp.arange(n))
    return dq, join(dk, k), join(dv, v)


_causal_blocks.defvjp(_causal_blocks_fwd, _causal_blocks_bwd)


def decode_attention(q, k_cache, v_cache, *, length=None, window: int | None = None, pos=None):
    """Single-token attention against a cache.  q: (B,1,H,hd);
    k/v_cache: (B,S,Hkv,hd).  ``length``: #valid cache entries (None = all).
    Works with sharded caches (reductions over the S axis lower to psums)."""
    B, _, H, hd = q.shape
    Skv, Hkv = k_cache.shape[1], k_cache.shape[2]
    n_rep = H // Hkv
    scale = 1.0 / np.sqrt(hd)
    q32 = q.astype(F32)[:, 0] * scale  # (B,H,hd)
    qg = q32.reshape(B, Hkv, n_rep, hd)
    s = jnp.einsum(
        "bkrd,bskd->bkrs", qg, k_cache.astype(F32), preferred_element_type=F32
    )  # (B,Hkv,rep,S)
    if length is not None:
        valid = jnp.arange(Skv)[None, :] < jnp.asarray(length).reshape(-1, 1)
        s = jnp.where(valid[:, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkrs,bskd->bkrd", p, v_cache.astype(F32), preferred_element_type=F32)
    o = o / jnp.maximum(l, 1e-30)
    return o.reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# attention block (params + apply)
# ---------------------------------------------------------------------------


def attention_params(key, d_model, n_heads, n_kv_heads, head_dim, qk_norm, dtype):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d_model, n_heads, head_dim), dtype),
        "wk": dense_init(ks[1], (d_model, n_kv_heads, head_dim), dtype),
        "wv": dense_init(ks[2], (d_model, n_kv_heads, head_dim), dtype),
        "wo": dense_init(ks[3], (n_heads, head_dim, d_model), dtype, scale=1.0 / np.sqrt(n_heads * head_dim)),
    }
    if qk_norm:
        p["q_norm"] = jnp.zeros((head_dim,), dtype)
        p["k_norm"] = jnp.zeros((head_dim,), dtype)
    return p


def attention_qkv(p, x, positions, *, rope_theta, qk_norm, compute_dtype):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(compute_dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(compute_dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(compute_dtype))
    if qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    return q, k, v


def attention_out(p, o, compute_dtype):
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(compute_dtype))


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_params(key, d_model, d_ff, dtype, gated: bool = True):
    ks = jax.random.split(key, 3)
    if gated:
        return {
            "w_gate": dense_init(ks[0], (d_model, d_ff), dtype),
            "w_up": dense_init(ks[1], (d_model, d_ff), dtype),
            "w_down": dense_init(ks[2], (d_ff, d_model), dtype),
        }
    return {
        "w_in": dense_init(ks[0], (d_model, d_ff), dtype),
        "w_out": dense_init(ks[2], (d_ff, d_model), dtype),
    }


def mlp_apply(p, x, compute_dtype, activation: str = "silu"):
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[activation]
    if "w_gate" in p:
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(compute_dtype))
        u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(compute_dtype))
        h = act(g) * u
        return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(compute_dtype))
    h = act(jnp.einsum("bsd,df->bsf", x, p["w_in"].astype(compute_dtype)))
    return jnp.einsum("bsf,fd->bsd", h, p["w_out"].astype(compute_dtype))


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


def softmax_cross_entropy(logits, labels, mask=None):
    """logits (..., V) f32/bf16; labels (...) int32.  Mean over valid tokens."""
    logits = logits.astype(F32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if mask is not None:
        mask = mask.astype(F32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
