"""Plain reference of the kanana-2-30b-a3b agent's loss (DeepSeek-V3 block),
in ``jax.numpy``.

Per layer: RMSNorm (scaled by 1 + gain); multi-head latent attention: the
query projection split per head into the no-position and the RoPE dims, the
latent ``c_kv`` (its own RMSNorm) and one shared RoPE key, per-head keys and
values from the latent, RoPE on interleaved pairs, full scores with a causal
mask and scale ``1/sqrt(nope + rope)``, the output projection and a
residual; then RMSNorm and either the dense SiLU-gated MLP or the expert
layer: sigmoid scores over all the router's experts in float32, the top k,
weights normalised over them and scaled, each held expert applied to every
token and weighted by its gate (zero where the token was not routed to it),
the shared experts, and DeepSeek-V3's sequence-wise balance loss (eqs.
17-20); a residual.  Then a final RMSNorm, the output head over the
vocabulary slice and the mean next-token cross-entropy, plus the balance
losses.  Selection uses the scores alone (no noaux_tc bias), as the
program does.  It imports nothing of the program.

The weights hold one group ``layer<i>_blocks`` of one slot per layer.  To
fit beside K agents' float32 parameters, momentum and gradients, each layer,
each block of queries, each expert and each block of positions of the
cross-entropy sits under ``jax.checkpoint``.  Every matmul operand goes
through ``policy.operand`` and runs at ``policy.precision``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 64  # queries per block of scores (the sequence a multiple of it, or shorter)
CE_BLOCK = 1024  # positions per block of logits (likewise)


def _norm(x, w, eps):
    x = x.astype(F32)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w.astype(F32))


def _mm(spec, x, w, policy):
    return jnp.einsum(spec, policy.operand(x), policy.operand(w), precision=policy.precision,
                      preferred_element_type=F32)


def _rope_pairs(x, theta):
    """Rotate interleaved pairs (x[2i], x[2i+1]) of the last axis by
    position x frequency i; x: (B, T, ..., dr)."""
    T, dr = x.shape[1], x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, dr, 2, dtype=F32) / dr)
    angle = (jnp.arange(T, dtype=F32)[:, None] * freq).reshape((1, T) + (1,) * (x.ndim - 3) + (dr // 2,))
    c, s = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, b * c + a * s], axis=-1).reshape(x.shape)


def _slot(tree, n: int):
    return jax.tree.map(lambda t: t[n], tree)


def _attention(p, h, cfg, policy):
    T, H, r = h.shape[1], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    q = _mm("btd,dhk->bthk", h, p["wq"], policy)
    kv = _mm("btd,dk->btk", h, p["wkv_a"], policy)
    c = _norm(kv[..., :r], p["kv_norm"], cfg["rms_norm_eps"])
    kvb = _mm("btr,rhk->bthk", c, p["wkv_b"], policy)
    theta = float(cfg["rope_theta"])
    q = jnp.concatenate([q[..., :dn], _rope_pairs(q[..., dn:], theta)], axis=-1)
    k_rope = jnp.broadcast_to(_rope_pairs(kv[..., r:], theta)[:, :, None], (*kvb.shape[:3], dr))
    k = jnp.concatenate([kvb[..., :dn], k_rope], axis=-1)
    v = kvb[..., dn:]

    n = min(Q_BLOCK, T)

    @jax.checkpoint
    def block(start):
        q_b = jax.lax.dynamic_slice_in_dim(q, start, n, axis=1)
        scores = _mm("bqhk,bshk->bhqs", q_b, k, policy) / jnp.sqrt(float(dn + dr))
        i = start + jnp.arange(n)[:, None]
        scores = jnp.where(jnp.arange(T)[None, :] <= i, scores, -jnp.inf)
        return _mm("bhqs,bshk->bqhk", jax.nn.softmax(scores, axis=-1), v, policy)

    o = jax.lax.map(block, jnp.arange(0, T, n))  # (T / n, B, n, H, dv)
    o = jnp.moveaxis(o, 0, 1).reshape(q.shape[0], T, H, v.shape[-1])
    return _mm("bqhk,hkd->bqd", o, p["wo"], policy)


def _ffn(h, w_gate, w_up, w_down, policy):
    gate = _mm("btd,df->btf", h, w_gate, policy)
    up = _mm("btd,df->btf", h, w_up, policy)
    return _mm("btf,fd->btd", jax.nn.silu(gate) * up, w_down, policy)


def _experts(p, h, cfg, policy):
    """(the held experts' and the shared experts' output, balance loss)."""
    E, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    T = h.shape[1]
    scores = jax.nn.sigmoid(_mm("btd,de->bte", h, p["router"], policy))
    top, idx = jax.lax.top_k(scores, k)
    gate = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) * cfg["routed_scaling_factor"]

    @jax.checkpoint
    def one(out, expert):
        e, w_gate, w_up, w_down = expert
        weight = jnp.sum(jnp.where(idx == cfg["held_offset"] + e, gate, 0.0), axis=-1)  # (B, T)
        return out + weight[..., None] * _ffn(h, w_gate, w_up, w_down, policy), None

    G = p["we_gate"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros(h.shape, F32),
                          (jnp.arange(G), p["we_gate"], p["we_up"], p["we_down"]))
    out = out + _ffn(h, p["ws_gate"], p["ws_up"], p["ws_down"], policy)
    chosen = jnp.sum(jax.nn.one_hot(idx, E, dtype=F32), axis=(1, 2))  # (B, E)
    f = chosen * E / (k * T)
    P = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=1)
    return out, cfg["aux_loss_weight"] * jnp.mean(jnp.sum(f * P, axis=-1))


def _layer(p, x, cfg, policy):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(p["attn"], _norm(x, p["ln1"], eps), cfg, policy)
    h = _norm(x, p["ln2"], eps)
    if "moe" in p:
        out, aux = _experts(p["moe"], h, cfg, policy)
        return x + out, aux
    mlp = p["mlp"]
    return x + _ffn(h, mlp["w_gate"], mlp["w_up"], mlp["w_down"], policy), jnp.zeros((), F32)


def loss(params, batch, policy, cfg):
    tokens = batch["tokens"]
    x = params["embed"]["tok"].astype(F32)[tokens[:, :-1]]
    aux = jnp.zeros((), F32)

    layer = jax.checkpoint(functools.partial(_layer, cfg=cfg, policy=policy))
    for i in range(cfg["num_hidden_layers"]):
        x, a = layer(_slot(params[f"layer{i}_blocks"], 0), x)
        aux = aux + a
    x = _norm(x, params["final_norm"]["w"], cfg["rms_norm_eps"])
    T = x.shape[1]
    n = min(CE_BLOCK, T)

    @jax.checkpoint
    def ce(start):
        x_b = jax.lax.dynamic_slice_in_dim(x, start, n, axis=1)
        t_b = jax.lax.dynamic_slice_in_dim(tokens[:, 1:], start, n, axis=1)
        logits = _mm("btd,dv->btv", x_b, params["lm_head"]["w"], policy)
        picked = jnp.take_along_axis(logits, t_b[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    nll = jnp.sum(jax.lax.map(ce, jnp.arange(0, T, n)))
    return nll / (tokens.shape[0] * T) + aux
