"""Generic decoder-LM engine.

Training/prefill forward: one ``lax.scan`` per group over stacked block
params (optionally rematerialized).  Serving (prefill -> decode_step): an
unrolled python loop over layers with per-layer heterogeneous caches — SWA
layers get ring buffers of size ``window``, Mamba layers carry O(1) state,
full-attention layers a (B, max_len, Hkv, hd) cache.  Unrolled serving graphs
are standard practice (latency-critical, no remat), and allow mixed cache
shapes that a scan cannot express.
"""
from __future__ import annotations

from typing import Any, Iterator, NamedTuple

import jax
import jax.numpy as jnp

from repro.models import hybrid as hybrid_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.config import GroupCfg, LayerCfg, ModelConfig
from repro.models.layers import (
    attention_out,
    attention_params,
    attention_qkv,
    decode_attention,
    dense_init,
    embed_init,
    flash_attention,
    mlp_apply,
    mlp_params,
    rms_norm,
    softmax_cross_entropy,
)

F32 = jnp.float32
PyTree = Any


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------


def _layer_params(key, cfg: ModelConfig, lc: LayerCfg):
    d, dtype = cfg.d_model, cfg.pdtype
    if lc.kind in ("mla_mlp", "mla_moe"):
        k1, k2 = jax.random.split(key)
        ffn = (
            {"moe": moe_mod.expert_share_params(k2, d, cfg.moe, dtype)}
            if lc.kind == "mla_moe"
            else {"mlp": mlp_params(k2, d, cfg.d_ff, dtype)}
        )
        return {
            "ln1": jnp.zeros((d,), dtype),
            "ln2": jnp.zeros((d,), dtype),
            "attn": mla_mod.mla_params(k1, d, cfg.mla, dtype),
            **ffn,
        }
    if lc.kind == "attn_mlp":
        k1, k2 = jax.random.split(key)
        a = cfg.attn
        return {
            "ln1": jnp.zeros((d,), dtype),
            "ln2": jnp.zeros((d,), dtype),
            "attn": attention_params(k1, d, a.n_heads, a.n_kv_heads, a.head_dim, a.qk_norm, dtype),
            "mlp": mlp_params(k2, d, cfg.d_ff, dtype),
        }
    if lc.kind == "moe":
        k1, k2 = jax.random.split(key)
        a = cfg.attn
        return {
            "ln1": jnp.zeros((d,), dtype),
            "ln2": jnp.zeros((d,), dtype),
            "attn": attention_params(k1, d, a.n_heads, a.n_kv_heads, a.head_dim, a.qk_norm, dtype),
            "moe": moe_mod.moe_params(k2, d, cfg.moe, dtype),
        }
    if lc.kind == "mamba":
        return {
            "ln": jnp.zeros((d,), dtype),
            "mamba": ssm_mod.mamba_params(key, d, cfg.ssm, dtype),
        }
    if lc.kind == "hymba":
        k1, k2 = jax.random.split(key)
        return {
            "ln1": jnp.zeros((d,), dtype),
            "ln2": jnp.zeros((d,), dtype),
            "mixer": hybrid_mod.hymba_mixer_params(k1, d, cfg.attn, cfg.ssm, cfg.pdtype),
            "mlp": mlp_params(k2, d, cfg.d_ff, dtype),
        }
    raise ValueError(lc.kind)


def _unit_params(key, cfg: ModelConfig, group: GroupCfg):
    if len(group.unit) == 1:
        return _layer_params(key, cfg, group.unit[0])
    keys = jax.random.split(key, len(group.unit))
    return {f"sub{i}": _layer_params(keys[i], cfg, lc) for i, lc in enumerate(group.unit)}


def init_decoder_params(key, cfg: ModelConfig) -> PyTree:
    n_groups = len(cfg.groups)
    keys = jax.random.split(key, n_groups + 3)
    params: dict = {
        "embed": {"tok": embed_init(keys[0], (cfg.vocab, cfg.d_model), cfg.pdtype)}
    }
    for gi, g in enumerate(cfg.groups):
        gkeys = jax.random.split(keys[1 + gi], g.repeat)
        params[g.param_key] = jax.vmap(lambda k: _unit_params(k, cfg, g))(gkeys)
    params["final_norm"] = {"w": jnp.zeros((cfg.d_model,), cfg.pdtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "w": dense_init(keys[-1], (cfg.d_model, cfg.vocab), cfg.pdtype)
        }
    return params


# ---------------------------------------------------------------------------
# forward (train / full-sequence)
# ---------------------------------------------------------------------------


def _apply_layer(p, x, cfg: ModelConfig, lc: LayerCfg, positions):
    """-> (x, aux loss, stats); stats is ``{}`` but for the expert-share
    layer's counters."""
    cd = cfg.cdtype
    zero = jnp.zeros((), F32)
    if lc.kind in ("attn_mlp", "moe"):
        a = cfg.attn
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = attention_qkv(
            p["attn"], h, positions, rope_theta=a.rope_theta, qk_norm=a.qk_norm, compute_dtype=cd
        )
        o = flash_attention(q, k, v, causal=True, window=lc.window)
        x = x + attention_out(p["attn"], o, cd)
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if lc.kind == "moe":
            out, aux = moe_mod.moe_apply(p["moe"], h, cfg.moe, cd)
            return x + out, aux, {}
        return x + mlp_apply(p["mlp"], h, cd), zero, {}
    if lc.kind in ("mla_mlp", "mla_moe"):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        x = x + mla_mod.mla_apply(p["attn"], h, cfg.mla, positions, cd, cfg.norm_eps)
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if lc.kind == "mla_moe":
            out, aux, stats = moe_mod.expert_share_apply(p["moe"], h, cfg.moe, cd)
            return x + out, aux, stats
        return x + mlp_apply(p["mlp"], h, cd), zero, {}
    if lc.kind == "mamba":
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        return x + ssm_mod.mamba_apply(p["mamba"], h, cfg.ssm, cfg.d_model, cd), zero, {}
    if lc.kind == "hymba":
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        x = x + hybrid_mod.hymba_mixer_apply(
            p["mixer"], h, cfg.attn, cfg.ssm, cfg.d_model, cd, lc.window
        )
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        return x + mlp_apply(p["mlp"], h, cd), zero, {}
    raise ValueError(lc.kind)


def _apply_unit(p, x, cfg: ModelConfig, group: GroupCfg, positions):
    aux = jnp.zeros((), F32)
    if len(group.unit) == 1:
        return _apply_layer(p, x, cfg, group.unit[0], positions)
    stats = {}
    for i, lc in enumerate(group.unit):
        x, a, st = _apply_layer(p[f"sub{i}"], x, cfg, lc, positions)
        aux = aux + a
        if st:
            stats[f"sub{i}"] = st
    return x, aux, stats


def decoder_stack(params, x, cfg: ModelConfig, with_stats: bool = False):
    """Run all scanned groups over hidden states x (B, S, d) -> (x, aux),
    or with ``with_stats`` (x, aux, stats): per group that has any, the
    layers' counters stacked over the group's layers.

    In unroll mode (dry-run cost pass) the layer scan becomes a python loop
    with static slices so cost_analysis sees every layer's FLOPs."""
    from repro.models.layers import unroll_inner

    S = x.shape[1]
    positions = jnp.arange(S)
    aux_total = jnp.zeros((), F32)
    stats = {}
    for g in cfg.groups:
        if unroll_inner():
            per_layer = []
            for r in range(g.repeat):
                p_slice = jax.tree.map(lambda t: t[r], params[g.param_key])
                x, a, st = _apply_unit(p_slice, x, cfg, g, positions)
                aux_total = aux_total + a
                per_layer.append(st)
            ys = jax.tree.map(lambda *t: jnp.stack(t), *per_layer)
        else:

            def body(carry, p_slice, g=g):
                h, aux = carry
                h, a, st = _apply_unit(p_slice, h, cfg, g, positions)
                return (h, aux + a), st

            if cfg.remat:
                body = jax.checkpoint(body)
            (x, aux_total), ys = jax.lax.scan(body, (x, aux_total), params[g.param_key])
        if ys:
            stats[g.name] = ys
    return (x, aux_total, stats) if with_stats else (x, aux_total)


def embed_tokens(params, tokens, cfg: ModelConfig):
    return params["embed"]["tok"].astype(cfg.cdtype)[tokens]


def unembed(params, x, cfg: ModelConfig):
    x = rms_norm(x, params["final_norm"]["w"], cfg.norm_eps)
    if cfg.tie_embeddings:
        w = params["embed"]["tok"].astype(cfg.cdtype).T
    else:
        w = params["lm_head"]["w"].astype(cfg.cdtype)
    return jnp.einsum("bsd,dv->bsv", x, w)


def forward(params, tokens, cfg: ModelConfig):
    """tokens (B, S) -> logits (B, S, V), aux."""
    x = embed_tokens(params, tokens, cfg)
    x, aux = decoder_stack(params, x, cfg)
    return unembed(params, x, cfg), aux


def lm_loss_and_stats(params, batch, rng, cfg: ModelConfig):
    """batch: {'tokens': (B, S+1)} -> (mean CE + MoE aux, stats): the
    layers' counters (``decoder_stack``), ``{}`` where no layer gives any."""
    tokens = batch["tokens"]
    x = embed_tokens(params, tokens[:, :-1], cfg)
    x, aux, stats = decoder_stack(params, x, cfg, with_stats=True)
    mask = batch.get("mask")
    if mask is not None:
        mask = mask[:, 1:]
    if cfg.head_chunk is not None:
        return _chunked_cross_entropy(params, x, tokens[:, 1:], mask, cfg) + aux, stats
    logits = unembed(params, x, cfg)
    return softmax_cross_entropy(logits, tokens[:, 1:], mask) + aux, stats


def _chunked_cross_entropy(params, x, labels, mask, cfg: ModelConfig):
    """The mean next-token cross-entropy, ``cfg.head_chunk`` positions at a
    time under ``jax.checkpoint``: the head's logits and their gradient
    exist for one block at once."""
    B, S, _ = x.shape
    c = min(cfg.head_chunk, S)
    mask = jnp.ones((B, S), F32) if mask is None else mask.astype(F32)
    n = -(-S // c)  # a ragged tail is padded, and masked out

    def blocks(t):
        t = jnp.pad(t, [(0, 0), (0, n * c - S)] + [(0, 0)] * (t.ndim - 2))
        return jnp.moveaxis(t.reshape(B, n, c, *t.shape[2:]), 1, 0)

    @jax.checkpoint
    def block(total, xs):
        x_b, y_b, m_b = xs
        logits = unembed(params, x_b, cfg).astype(F32)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, y_b[..., None], -1)[..., 0]
        return total + jnp.sum(nll * m_b), None

    total, _ = jax.lax.scan(block, jnp.zeros((), F32), (blocks(x), blocks(labels), blocks(mask)))
    return total / jnp.maximum(jnp.sum(mask), 1.0)


def lm_loss(params, batch, rng, cfg: ModelConfig):
    """batch: {'tokens': (B, S+1)} -> mean CE + MoE aux."""
    return lm_loss_and_stats(params, batch, rng, cfg)[0]


# ---------------------------------------------------------------------------
# serving: layer iteration, caches, prefill, decode
# ---------------------------------------------------------------------------


class LayerRef(NamedTuple):
    group: GroupCfg
    rep: int
    sub: int
    lc: LayerCfg


def iter_layers(cfg: ModelConfig) -> Iterator[LayerRef]:
    for g in cfg.groups:
        for r in range(g.repeat):
            for s, lc in enumerate(g.unit):
                yield LayerRef(g, r, s, lc)


def _layer_param_slice(params, ref: LayerRef):
    sub = jax.tree.map(lambda x: x[ref.rep], params[ref.group.param_key])
    if len(ref.group.unit) > 1:
        sub = sub[f"sub{ref.sub}"]
    return sub


def _attn_cache_len(lc: LayerCfg, max_len: int) -> int:
    return min(lc.window, max_len) if lc.window is not None else max_len


def init_caches(cfg: ModelConfig, B: int, max_len: int, dtype=None) -> list[dict]:
    """Per-layer cache list.  SWA layers get ring buffers of size window."""
    dtype = dtype or cfg.cdtype
    a = cfg.attn
    caches = []
    for ref in iter_layers(cfg):
        lc = ref.lc
        c: dict = {}
        if lc.kind in ("attn_mlp", "moe", "hymba"):
            W = _attn_cache_len(lc, max_len)
            c["k"] = jnp.zeros((B, W, a.n_kv_heads, a.head_dim), dtype)
            c["v"] = jnp.zeros((B, W, a.n_kv_heads, a.head_dim), dtype)
        if lc.kind in ("mamba", "hymba"):
            st = ssm_mod.mamba_init_state(B, cfg.d_model, cfg.ssm)
            c["conv"], c["ssm"] = st["conv"], st["ssm"]
        caches.append(c)
    return caches


def _decode_layer(p, x, cache, pos, cfg: ModelConfig, lc: LayerCfg):
    cd = cfg.cdtype
    a = cfg.attn
    if lc.kind in ("attn_mlp", "moe"):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = attention_qkv(
            p["attn"], h, jnp.reshape(pos, (1,)), rope_theta=a.rope_theta,
            qk_norm=a.qk_norm, compute_dtype=cd,
        )
        W = cache["k"].shape[1]
        slot = pos % W if lc.window is not None else pos
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), slot, axis=1
        )
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), slot, axis=1
        )
        length = jnp.minimum(pos + 1, W)
        o = decode_attention(q, k_cache, v_cache, length=length)
        x = x + attention_out(p["attn"], o, cd)
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if lc.kind == "moe":
            out, _ = moe_mod.moe_apply(p["moe"], h, cfg.moe, cd)
            x = x + out
        else:
            x = x + mlp_apply(p["mlp"], h, cd)
        return x, {**cache, "k": k_cache, "v": v_cache}
    if lc.kind == "mamba":
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        out, st = ssm_mod.mamba_decode_step(
            p["mamba"], h, {"conv": cache["conv"], "ssm": cache["ssm"]}, cfg.ssm, cfg.d_model, cd
        )
        return x + out, {**cache, **st}
    if lc.kind == "hymba":
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        out, new_cache = hybrid_mod.hymba_mixer_decode(
            p["mixer"], h, cache, pos, cfg.attn, cfg.ssm, cfg.d_model, cd, lc.window
        )
        x = x + out
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + mlp_apply(p["mlp"], h, cd)
        return x, new_cache
    raise ValueError(lc.kind)


def _refuse_latent_attention(cfg: ModelConfig):
    if any(ref.lc.kind in ("mla_mlp", "mla_moe") for ref in iter_layers(cfg)):
        raise NotImplementedError(
            f"{cfg.name}: serving latent-attention layers needs an MLA decode "
            "cache, which this engine does not have (training only)"
        )


def decode_step(params, token, caches, pos, cfg: ModelConfig):
    """One serving step: token (B, 1) + caches @ pos -> (logits (B,1,V), caches)."""
    _refuse_latent_attention(cfg)
    x = embed_tokens(params, token, cfg)
    new_caches = []
    for i, ref in enumerate(iter_layers(cfg)):
        p = _layer_param_slice(params, ref)
        x, c = _decode_layer(p, x, caches[i], pos, cfg, ref.lc)
        new_caches.append(c)
    return unembed(params, x, cfg), new_caches


def _ring_fill(cache_kv, kv, S, allow_wrap: bool = True):
    """Write the last W of kv (B, S, Hkv, hd) into a ring buffer of size W
    using the decode slot convention slot = pos % W."""
    W = cache_kv.shape[1]
    if not allow_wrap and W < S:
        raise ValueError(
            f"full-attention KV cache too small: max_len={W} < prefill len {S}"
        )
    take = min(W, S)
    tail = kv[:, S - take : S]
    slots = (jnp.arange(S - take, S)) % W
    return cache_kv.at[:, slots].set(tail.astype(cache_kv.dtype))


def prefill(params, tokens, cfg: ModelConfig, max_len: int):
    """Full-sequence prefill building decode caches.

    Returns (logits of the LAST position (B, 1, V), caches, next_pos)."""
    _refuse_latent_attention(cfg)
    B, S = tokens.shape
    x = embed_tokens(params, tokens, cfg)
    positions = jnp.arange(S)
    caches = init_caches(cfg, B, max_len)
    cd = cfg.cdtype
    a = cfg.attn
    for i, ref in enumerate(iter_layers(cfg)):
        p = _layer_param_slice(params, ref)
        lc = ref.lc
        if lc.kind in ("attn_mlp", "moe"):
            h = rms_norm(x, p["ln1"], cfg.norm_eps)
            q, k, v = attention_qkv(
                p["attn"], h, positions, rope_theta=a.rope_theta, qk_norm=a.qk_norm, compute_dtype=cd
            )
            caches[i]["k"] = _ring_fill(caches[i]["k"], k, S, allow_wrap=lc.window is not None)
            caches[i]["v"] = _ring_fill(caches[i]["v"], v, S, allow_wrap=lc.window is not None)
            o = flash_attention(q, k, v, causal=True, window=lc.window)
            x = x + attention_out(p["attn"], o, cd)
            h = rms_norm(x, p["ln2"], cfg.norm_eps)
            if lc.kind == "moe":
                out, _ = moe_mod.moe_apply(p["moe"], h, cfg.moe, cd)
                x = x + out
            else:
                x = x + mlp_apply(p["mlp"], h, cd)
        elif lc.kind == "mamba":
            h = rms_norm(x, p["ln"], cfg.norm_eps)
            out, st = _mamba_prefill(p["mamba"], h, cfg)
            caches[i]["conv"], caches[i]["ssm"] = st["conv"], st["ssm"]
            x = x + out
        elif lc.kind == "hymba":
            h = rms_norm(x, p["ln1"], cfg.norm_eps)
            q, k, v = attention_qkv(
                p["mixer"]["attn"], h, positions, rope_theta=a.rope_theta,
                qk_norm=a.qk_norm, compute_dtype=cd,
            )
            caches[i]["k"] = _ring_fill(caches[i]["k"], k, S, allow_wrap=lc.window is not None)
            caches[i]["v"] = _ring_fill(caches[i]["v"], v, S, allow_wrap=lc.window is not None)
            o = flash_attention(q, k, v, causal=True, window=lc.window)
            a_out = attention_out(p["mixer"]["attn"], o, cd)
            m_out, st = _mamba_prefill(p["mixer"]["mamba"], h, cfg)
            caches[i]["conv"], caches[i]["ssm"] = st["conv"], st["ssm"]
            y = rms_norm(a_out, p["mixer"]["ln_attn"]) * p["mixer"]["beta_attn"].astype(cd)
            y = y + rms_norm(m_out, p["mixer"]["ln_ssm"]) * p["mixer"]["beta_ssm"].astype(cd)
            x = x + 0.5 * y
            h = rms_norm(x, p["ln2"], cfg.norm_eps)
            x = x + mlp_apply(p["mlp"], h, cd)
        else:
            raise ValueError(lc.kind)
    logits = unembed(params, x[:, -1:], cfg)
    return logits, caches, S


def _mamba_prefill(p, h, cfg: ModelConfig):
    """Mamba over the full prompt, returning output and final decode state."""
    cd = cfg.cdtype
    ssm = cfg.ssm
    di = ssm.expand * cfg.d_model
    xz = jnp.einsum("bsd,de->bse", h, p["in_proj"].astype(cd))
    x_in, z = jnp.split(xz, [di], axis=-1)
    x_conv = jax.nn.silu(ssm_mod._causal_conv(x_in, p["conv_w"], p["conv_b"]))
    dt, A, Bm, Cm = ssm_mod._ssm_inputs(p, x_conv, ssm, cfg.d_model)
    y, h_last = ssm_mod.selective_scan_chunked(dt, A, Bm, Cm, x_conv)
    y = y + p["D"].astype(F32) * x_conv.astype(F32)
    y = y * jax.nn.silu(z.astype(F32))
    out = jnp.einsum("bsd,de->bse", y.astype(cd), p["out_proj"].astype(cd))
    conv_state = x_in[:, -(ssm.d_conv - 1) :].astype(F32)
    return out, {"conv": conv_state, "ssm": h_last}
