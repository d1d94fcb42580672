"""Per-architecture smoke tests (reduced configs, CPU) + serving consistency.

The assignment requires, per architecture, a REDUCED variant (2 layers,
d_model <= 512, <= 4 experts) running one forward/train step on CPU with
shape + finiteness assertions.  Full configs are exercised via the dry-run.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import get_bundle, get_config, list_archs
from repro.models.registry import build_bundle
from repro.utils import tree_size

SMOKE_ARCHS = [a for a in list_archs() if a.endswith("-smoke")]
B, S = 2, 32


def _batch(cfg, key, tokens):
    if cfg.family == "audio":
        return {
            "audio_embeds": jax.random.normal(key, (B, cfg.encoder.n_frames, cfg.d_model)),
            "tokens": tokens,
        }
    if cfg.family == "vlm":
        return {
            "patch_embeds": jax.random.normal(key, (B, cfg.n_img_tokens, 1024)),
            "tokens": tokens,
        }
    return {"tokens": tokens}


def test_all_assigned_archs_have_smoke_variants():
    from repro.configs import ASSIGNED_ARCHS

    for arch in ASSIGNED_ARCHS:
        assert f"{arch}-smoke" in SMOKE_ARCHS
        cfg = get_config(f"{arch}-smoke")
        assert cfg.n_layers <= 4
        assert cfg.d_model <= 512
        assert cfg.moe is None or cfg.moe.n_experts <= 4


def test_full_configs_match_assignment():
    """Exact assigned hyperparameters (the public-pool table)."""
    expect = {
        "llava-next-34b": (60, 7168, 56, 8, 20480, 64000),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "qwen3-8b": (36, 4096, 32, 8, 12288, 151936),
        "h2o-danube-3-4b": (24, 3840, 32, 8, 10240, 32000),
        "kimi-k2-1t-a32b": (61, 7168, 64, 8, 2048, 163840),
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
        "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
        "gemma3-27b": (62, 5376, 32, 16, 21504, 262144),
    }
    for arch, (L, d, H, kv, ff, V) in expect.items():
        cfg = get_config(arch)
        n_layers = cfg.n_layers if cfg.family != "audio" else cfg.groups[0].repeat
        assert n_layers == L, arch
        assert cfg.d_model == d, arch
        assert cfg.attn.n_heads == H and cfg.attn.n_kv_heads == kv, arch
        assert cfg.d_ff == ff, arch
        assert cfg.vocab == V, arch
    fm = get_config("falcon-mamba-7b")
    assert fm.n_layers == 64 and fm.d_model == 4096 and fm.vocab == 65024
    assert fm.attn is None and fm.ssm.d_state == 16
    kn = get_config("kanana-2-30b-a3b")  # latent attention: no GQA heads to list
    assert kn.n_layers == 48 and kn.d_model == 2048 and kn.vocab == 128256 and kn.d_ff == 6144
    assert kn.groups[0].n_layers == 1 and kn.groups[0].unit[0].kind == "mla_mlp"
    m = kn.mla
    assert (m.n_heads, m.kv_lora_rank, m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim) == (
        32, 512, 128, 64, 128,
    )
    e = kn.moe
    assert (e.n_experts, e.top_k, e.d_ff_expert, e.shared_d_ff, e.held) == (128, 6, 768, 1536, 128)
    assert e.routed_scale == 2.448 and kn.head_chunk == 1024


def test_moe_expert_counts():
    l4 = get_config("llama4-maverick-400b-a17b")
    assert l4.moe.n_experts == 128 and l4.moe.top_k == 1
    k2 = get_config("kimi-k2-1t-a32b")
    assert k2.moe.n_experts == 384 and k2.moe.top_k == 8


def test_param_counts_in_range():
    """Analytic parameter counts are in the advertised ballpark."""
    expect_b = {
        "qwen3-8b": (7, 10),
        "qwen3-4b": (3.5, 5.5),
        "gemma3-27b": (24, 30),
        "falcon-mamba-7b": (6, 9),
        "h2o-danube-3-4b": (3, 5),
        "hymba-1.5b": (1.2, 2.2),
        "llava-next-34b": (32, 38),
        "kimi-k2-1t-a32b": (950, 1100),
        "llama4-maverick-400b-a17b": (370, 440),
        "whisper-large-v3": (1.2, 2.2),
    }
    for arch, (lo, hi) in expect_b.items():
        n = get_config(arch).param_count() / 1e9
        assert lo <= n <= hi, (arch, n)


@pytest.mark.parametrize("name", SMOKE_ARCHS)
def test_smoke_train_step(name):
    """One forward + grad step: finite loss, finite grads, correct shapes."""
    b = get_bundle(name)
    cfg = b.cfg
    key = jax.random.key(0)
    params = b.init(key)
    assert tree_size(params) > 0
    tokens = jax.random.randint(key, (B, S + 1), 0, cfg.vocab)
    batch = _batch(cfg, key, tokens)
    logits = b.forward(params, {**batch, "tokens": tokens[:, :-1]})
    S_out = logits.shape[1]
    assert logits.shape == (B, S_out, cfg.vocab)
    loss, grads = jax.value_and_grad(b.loss)(params, batch, key)
    assert jnp.isfinite(loss), name
    gnorm = jnp.sqrt(
        sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree.leaves(grads))
    )
    assert jnp.isfinite(gnorm) and float(gnorm) > 0, name


@pytest.mark.parametrize(
    "name",
    [
        "qwen3-8b-smoke",
        "gemma3-27b-smoke",
        "falcon-mamba-7b-smoke",
        "hymba-1.5b-smoke",
        "whisper-large-v3-smoke",
        "llava-next-34b-smoke",
        "kimi-k2-1t-a32b-smoke",
    ],
)
def test_prefill_decode_matches_forward(name):
    """Decode with caches reproduces teacher-forcing logits (the KV-cache /
    ring-buffer / SSM-state correctness test).  MoE runs with generous
    capacity (serving MoE must not drop)."""
    cfg = get_config(name)
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0)
        )
    b = build_bundle(cfg)
    key = jax.random.key(1)
    params = b.init(key)
    tokens = jax.random.randint(key, (B, S), 1, cfg.vocab)
    batch = _batch(cfg, key, tokens)
    full_logits = b.forward(params, batch)
    n0 = S - 4
    pre = dict(batch)
    pre["tokens"] = tokens[:, :n0]
    extra = cfg.n_img_tokens if cfg.family == "vlm" else 0
    logits_p, caches, pos = b.prefill(params, pre, extra + S + 8)
    errs = [float(jnp.max(jnp.abs(logits_p[:, -1] - full_logits[:, n0 - 1])))]
    for t in range(n0, S):
        logits_d, caches = b.decode_step(params, tokens[:, t : t + 1], caches, jnp.asarray(pos))
        pos += 1
        errs.append(float(jnp.max(jnp.abs(logits_d[:, 0] - full_logits[:, t]))))
    assert max(errs) < 1e-3, (name, errs)


def test_swa_ring_buffer_evicts_old_tokens():
    """After more than `window` tokens, a SWA layer's output is independent
    of the earliest tokens (locality property of the sliding window)."""
    cfg = get_config("h2o-danube-3-4b-smoke")  # window 16
    b = build_bundle(cfg)
    params = b.init(jax.random.key(0))
    key = jax.random.key(2)
    S_long = 40  # > 2x window
    t1 = jax.random.randint(key, (1, S_long), 1, cfg.vocab)
    t2 = t1.at[:, :4].set(jax.random.randint(jax.random.key(3), (1, 4), 1, cfg.vocab))
    l1 = b.forward(params, {"tokens": t1})
    l2 = b.forward(params, {"tokens": t2})
    # last position attends only the last `window` tokens => identical logits
    np.testing.assert_allclose(
        np.asarray(l1[:, -1]), np.asarray(l2[:, -1]), atol=1e-4
    )
    # but early positions DO differ
    assert float(jnp.max(jnp.abs(l1[:, 4] - l2[:, 4]))) > 1e-4


def test_moe_capacity_drops_tokens_when_tight():
    """Capacity bookkeeping: with the tightest capacity (cap == top_k, the
    floor enforced by moe_apply) most routed slots are dropped — a strict
    majority of tokens lose at least one expert vs generous capacity."""
    from repro.models.moe import moe_apply, moe_params
    from repro.models.config import MoECfg

    key = jax.random.key(0)
    d, E = 32, 4
    x = jax.random.normal(key, (2, 16, d))
    m_tight = MoECfg(n_experts=E, top_k=2, d_ff_expert=64, capacity_factor=1e-6, group_size=32)
    p = moe_params(key, d, m_tight, jnp.float32)
    out_tight, _ = moe_apply(p, x, m_tight, jnp.float32)
    m_loose = dataclasses.replace(m_tight, capacity_factor=8.0)
    out_loose, _ = moe_apply(p, x, m_loose, jnp.float32)
    # cap == 2 slots/expert/group => at most E*cap = 8 of 64 routed slots kept
    n_tight = jnp.mean(jnp.abs(out_tight))
    n_loose = jnp.mean(jnp.abs(out_loose))
    assert float(n_tight) < 0.5 * float(n_loose), (n_tight, n_loose)
    # dropped tokens have exactly-zero routed output
    row_norm = jnp.linalg.norm(out_tight, axis=-1).reshape(-1)
    assert int(jnp.sum(row_norm == 0.0)) >= 16


def test_flash_attention_matches_naive():
    from repro.models.layers import flash_attention

    key = jax.random.key(0)
    B_, S_, H, hd = 2, 67, 4, 16
    q = jax.random.normal(key, (B_, S_, H, hd))
    k = jax.random.normal(jax.random.key(1), (B_, S_, 2, hd))
    v = jax.random.normal(jax.random.key(2), (B_, S_, 2, hd))

    def naive(q, k, v, window=None):
        kk = jnp.repeat(k, H // 2, axis=2)
        vv = jnp.repeat(v, H // 2, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(hd)
        i = jnp.arange(S_)
        mask = i[None, :] <= i[:, None]
        if window:
            mask &= i[None, :] > i[:, None] - window
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vv)

    for window in (None, 16):
        got = flash_attention(q, k, v, causal=True, window=window, kv_chunk=32, q_chunk=32)
        want = naive(q, k, v, window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # a value head width other than the query/key width (latent attention)
    v = jax.random.normal(jax.random.key(3), (B_, S_, 2, 24))
    for window in (None, 16):
        got = flash_attention(q, k, v, causal=True, window=window, kv_chunk=32, q_chunk=32)
        assert got.shape == (B_, S_, H, 24)
        want = naive(q, k, v, window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
