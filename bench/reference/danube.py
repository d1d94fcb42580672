"""Plain reference of the decoder-only LM agent's loss, in ``jax.numpy``.

Per layer: RMSNorm (scaled by 1 + gain), query/key/value projections, RoPE
on the two halves of each head, grouped-query attention with a causal mask
over the last ``window`` positions, the output projection and a residual;
then RMSNorm, a SiLU-gated MLP and a residual.  Then a final RMSNorm, the
output head and the mean next-token cross-entropy.  It imports nothing of
the program.

Every matmul operand goes through ``policy.operand`` and runs at
``policy.precision``: float32 at ``HIGHEST`` for the reference, an int8
grid in bfloat16 for the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _norm(x, w, eps):
    x = x.astype(F32)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w.astype(F32))


def _mm(spec, x, w, policy):
    return jnp.einsum(spec, policy.operand(x), policy.operand(w), precision=policy.precision,
                      preferred_element_type=F32)


def _rope(x, theta):
    T, hd = x.shape[-3], x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    angle = jnp.arange(T, dtype=F32)[:, None, None] * freq
    c, s = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def _layer(p, x, cfg, policy):
    T = x.shape[1]
    H, G, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    h = _norm(x, p["ln1"], cfg["norm_eps"])
    q = _rope(_mm("btd,dhk->bthk", h, p["attn"]["wq"], policy), cfg["rope_theta"])
    k = _rope(_mm("btd,dhk->bthk", h, p["attn"]["wk"], policy), cfg["rope_theta"])
    v = _mm("btd,dhk->bthk", h, p["attn"]["wv"], policy)
    k, v = jnp.repeat(k, H // G, axis=2), jnp.repeat(v, H // G, axis=2)
    scores = _mm("bqhk,bshk->bhqs", q, k, policy) / jnp.sqrt(float(hd))
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = (j <= i) & (j > i - cfg["window"])
    scores = jnp.where(seen, scores, -jnp.inf)
    o = _mm("bhqs,bshk->bqhk", jax.nn.softmax(scores, axis=-1), v, policy)
    x = x + _mm("bqhk,hkd->bqd", o, p["attn"]["wo"], policy)
    h = _norm(x, p["ln2"], cfg["norm_eps"])
    gate = _mm("btd,df->btf", h, p["mlp"]["w_gate"], policy)
    up = _mm("btd,df->btf", h, p["mlp"]["w_up"], policy)
    return x + _mm("btf,fd->btd", jax.nn.silu(gate) * up, p["mlp"]["w_down"], policy)


def loss(params, batch, policy, cfg):
    tokens = batch["tokens"]
    x = params["embed"]["tok"].astype(F32)[tokens[:, :-1]]
    blocks = params["main_blocks"]
    for n in range(blocks["ln1"].shape[0]):
        x = _layer(jax.tree.map(lambda a: a[n], blocks), x, cfg, policy)
    x = _norm(x, params["final_norm"]["w"], cfg["norm_eps"])
    logits = _mm("btd,dv->btv", x, params["lm_head"]["w"], policy)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)
