"""Multi-head latent attention (DeepSeek-V2 §2.1, DeepSeek-V3 §2.1.1),
without query compression, for training.

Per token ``h``:

* ``q = W_q h``, split per head into ``qk_nope_head_dim`` dims without
  position and ``qk_rope_head_dim`` dims that take RoPE;
* ``[c_kv, k_rope] = W_kva h``: the ``kv_lora_rank`` latent and one RoPE
  key that every head shares; ``c_kv`` goes through its own RMSNorm;
* ``[k_nope, v] = W_kvb c_kv`` per head;
* causal attention of ``[q_nope, rope(q_rope)]`` over
  ``[k_nope, rope(k_rope)]`` with scale ``1/sqrt(nope + rope)`` and values of
  ``v_head_dim``, then ``W_o`` back to the model width.

RoPE turns interleaved pairs ``(x[2i], x[2i+1])`` (``rope_interleave``):
the rope dims are de-interleaved into halves, as the published code does,
and rotated as halves; queries and keys take the same layout, so the scores
are those of the pairwise rotation.  Per-head keys and values are
materialised from the latent (no absorbed form, no cache): serving an MLA
model is not supported (``transformer.prefill`` refuses it).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import MLACfg
from repro.models.layers import apply_rope, dense_init, flash_attention, rms_norm


def mla_params(key, d_model: int, m: MLACfg, dtype):
    H, r = m.n_heads, m.kv_lora_rank
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d_model, H, dn + dr), dtype),
        "wkv_a": dense_init(ks[1], (d_model, r + dr), dtype),
        "kv_norm": jnp.zeros((r,), dtype),
        "wkv_b": dense_init(ks[2], (r, H, dn + dv), dtype),
        "wo": dense_init(ks[3], (H, dv, d_model), dtype, scale=1.0 / np.sqrt(H * dv)),
    }


def rope_interleaved(x, positions, theta: float):
    """RoPE on interleaved pairs of the last axis, returned as halves."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, positions, theta)


def mla_apply(p, x, m: MLACfg, positions, compute_dtype, norm_eps: float):
    """x: (B, S, d) -> (B, S, d)."""
    cd = compute_dtype
    dn, r = m.qk_nope_head_dim, m.kv_lora_rank
    with jax.named_scope("mla"):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
        kv = jnp.einsum("bsd,dk->bsk", x, p["wkv_a"].astype(cd))
        c_kv = rms_norm(kv[..., :r], p["kv_norm"], norm_eps)
        k_rope = rope_interleaved(kv[..., None, r:], positions, m.rope_theta)  # (B,S,1,dr)
        kv_b = jnp.einsum("bsr,rhk->bshk", c_kv, p["wkv_b"].astype(cd))
        q = jnp.concatenate(
            [q[..., :dn], rope_interleaved(q[..., dn:], positions, m.rope_theta)], axis=-1
        )
        k = jnp.concatenate(
            [kv_b[..., :dn], jnp.broadcast_to(k_rope, (*kv_b.shape[:3], k_rope.shape[-1]))],
            axis=-1,
        )
        o = flash_attention(q, k, kv_b[..., dn:], causal=True)
        return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(cd))
