"""Latent attention, the DeepSeek-V3 router and the dropless expert-share
layer, and the grouped matmul kernel, on the CPU at small sizes with seeded
random weights, against plain float32 references."""
import dataclasses
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.moe_gmm import ROW_TILE, moe_gmm
from repro.models import get_bundle
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models.config import MLACfg, MoECfg

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
MLA = MLACfg(n_heads=4, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=6)
MOE = MoECfg(
    n_experts=16, top_k=4, d_ff_expert=16, shared_d_ff=32,
    routed_scale=2.448, aux_loss_weight=1e-4,
)
D = 32


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _plain_reference():
    """bench/reference/kanana.py: the benchmark's plain reference, which
    imports nothing of the program."""
    path = pathlib.Path(__file__).resolve().parents[1] / "bench" / "reference" / "kanana.py"
    spec = importlib.util.spec_from_file_location("kanana_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _F32Policy:
    precision = HIGHEST

    @staticmethod
    def operand(x):
        return x.astype(F32)


def _ref_cfg(mla: MLACfg, **kw):
    return dict(
        num_attention_heads=mla.n_heads, kv_lora_rank=mla.kv_lora_rank,
        qk_nope_head_dim=mla.qk_nope_head_dim, qk_rope_head_dim=mla.qk_rope_head_dim,
        rope_theta=mla.rope_theta, rms_norm_eps=1e-6, **kw,
    )


def test_mla_block_matches_plain_reference():
    ref = _plain_reference()
    p = mla_mod.mla_params(jax.random.key(0), D, MLA, F32)
    p["kv_norm"] = 0.1 * jax.random.normal(jax.random.key(1), p["kv_norm"].shape)
    x = jax.random.normal(jax.random.key(2), (2, 24, D))
    got = mla_mod.mla_apply(p, x, MLA, jnp.arange(24), F32, 1e-6)
    want = ref._attention(p, x, _ref_cfg(MLA), _F32Policy)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_small_model_loss_and_gradients_match_plain_reference():
    """The smoke config with 4 of 16 experts held at an offset of 8, through
    the registry's loss, against the plain reference's."""
    ref = _plain_reference()
    b = get_bundle("kanana-2-30b-a3b-smoke")
    moe = dataclasses.replace(b.cfg.moe, n_experts=16, n_held=4, held_offset=8)
    cfg = dataclasses.replace(b.cfg, moe=moe)
    b = get_bundle("kanana-2-30b-a3b-smoke", moe=moe)
    params = b.init(jax.random.key(3))
    tokens = jax.random.randint(jax.random.key(4), (2, 33), 0, cfg.vocab)
    rcfg = _ref_cfg(
        cfg.mla, router_experts=16, num_experts_per_tok=moe.top_k,
        routed_scaling_factor=moe.routed_scale, held_offset=8, aux_loss_weight=moe.aux_loss_weight,
    )
    rcfg["num_hidden_layers"] = cfg.n_layers

    def per_layer(p):
        """The reference's layout: one group ``layer<i>_blocks`` a layer."""
        out = {k: p[k] for k in ("embed", "final_norm", "lm_head")}
        out["layer0_blocks"] = p["dense_blocks"]
        for i in range(2):
            out[f"layer{i + 1}_blocks"] = jax.tree.map(lambda t: t[i : i + 1], p["moe_blocks"])
        return out

    got, g_got = jax.value_and_grad(b.loss)(params, {"tokens": tokens}, None)
    want, g_want = jax.value_and_grad(
        lambda p: ref.loss(per_layer(p), {"tokens": tokens}, _F32Policy, rcfg)
    )(params)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for (path, a), c in zip(jax.tree_util.tree_flatten_with_path(g_got)[0], jax.tree.leaves(g_want)):
        scale = float(jnp.max(jnp.abs(c))) + 1e-12
        assert float(jnp.max(jnp.abs(a - c))) <= 1e-4 * scale, jax.tree_util.keystr(path)


def _layer_params(key, moe=MOE):
    return moe_mod.expert_share_params(key, D, moe, F32)


def _uncut_routed(p, x, gate, idx):
    """Every expert applied to every token, weighted by its gate."""
    out = jnp.zeros_like(x)
    for e in range(p["we_gate"].shape[0]):
        w = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)
        h = jax.nn.silu(x @ p["we_gate"][e]) * (x @ p["we_up"][e])
        out = out + w[:, None] * (h @ p["we_down"][e])
    return out


def test_expert_shares_add_up_to_the_whole_layer():
    """16 experts, top-4, four shares of 4 held experts: the shares' routed
    parts, with the shared experts and the balance loss counted once, give
    the uncut layer."""
    p = _layer_params(jax.random.key(5))
    x = jax.random.normal(jax.random.key(6), (2, 20, D))
    held = dataclasses.replace(MOE, n_held=4)
    parts, aux, routed = [], [], 0.0
    for s in range(4):
        share = {**p, **{w: p[w][4 * s : 4 * s + 4] for w in ("we_gate", "we_up", "we_down")}}
        out, a, stats = moe_mod.expert_share_apply(
            share, x, dataclasses.replace(held, held_offset=4 * s), F32
        )
        parts.append(out - moe_mod.shared_experts_apply(p, x, F32))
        aux.append(float(a))
        routed += float(stats["routed_slots"])
    whole, whole_aux, _ = moe_mod.expert_share_apply(p, x, MOE, F32)
    np.testing.assert_allclose(
        np.asarray(sum(parts) + moe_mod.shared_experts_apply(p, x, F32)), np.asarray(whole),
        rtol=1e-5, atol=1e-5,
    )
    assert aux == [float(whole_aux)] * 4  # every share computes it alike
    _, gate, idx = moe_mod.route(x, p["router"], MOE)
    want = _uncut_routed(p, x.reshape(-1, D), gate.reshape(40, -1), idx.reshape(40, -1))
    want = want + moe_mod.shared_experts_apply(p, x, F32).reshape(-1, D)
    np.testing.assert_allclose(np.asarray(whole).reshape(-1, D), np.asarray(want), rtol=1e-5, atol=1e-5)
    # the slots routed to held experts across the shares are all the slots
    assert routed == 2 * 20 * MOE.top_k


def test_router_is_sigmoid_top_k_normalised_and_scaled():
    p = _layer_params(jax.random.key(7))
    x = jax.random.normal(jax.random.key(8), (3, D))
    scores, gate, idx = moe_mod.route(x, p["router"], MOE)
    s = jax.nn.sigmoid(x @ p["router"])
    np.testing.assert_allclose(np.asarray(scores), np.asarray(s), rtol=1e-6)
    top = np.sort(np.asarray(s), axis=-1)[:, ::-1][:, : MOE.top_k]
    np.testing.assert_allclose(np.sort(np.asarray(gate), -1)[:, ::-1], top / top.sum(-1, keepdims=True) * 2.448, rtol=1e-5)


@pytest.mark.parametrize("case", ["one_expert_takes_all", "full_buffer", "none_held"])
def test_expert_share_is_dropless(case):
    """Total imbalance, a buffer with every slot held, and a share that
    holds none of the routed experts: output and gradients match the uncut
    per-token computation, with nothing dropped and nothing undefined."""
    T, k, G, offset = 96, 2, 4, 4
    moe = dataclasses.replace(MOE, top_k=k, n_held=G, held_offset=offset)
    p = _layer_params(jax.random.key(9), moe)
    x = jax.random.normal(jax.random.key(10), (T, D))
    gate = jax.random.uniform(jax.random.key(11), (T, k), minval=0.5, maxval=1.5)
    cols = {
        "one_expert_takes_all": [offset + 1, 0],  # every held slot to held expert 1
        "full_buffer": [offset + 0, offset + 3],  # every slot held, experts 1 and 2 empty
        "none_held": [0, 1],
    }[case]
    idx = jnp.broadcast_to(jnp.asarray(cols, jnp.int32), (T, k))

    def got(x, gate, p):
        out, sizes = moe_mod.held_experts_apply(p, x, gate, idx, moe, F32)
        return out, sizes

    def want(x, gate, p):
        return _uncut_routed(p, x, gate, idx - offset)

    out, sizes = got(x, gate, p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want(x, gate, p)), rtol=1e-5, atol=1e-5)
    held_slots = int(np.sum((np.asarray(idx) >= offset) & (np.asarray(idx) < offset + G)))
    assert int(jnp.sum(sizes)) == held_slots
    cot = jax.random.normal(jax.random.key(12), (T, D))
    grads = jax.grad(lambda *a: jnp.sum(got(*a)[0] * cot), (0, 1, 2))(x, gate, p)
    grads_want = jax.grad(lambda *a: jnp.sum(want(*a) * cot), (0, 1, 2))(x, gate, p)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_want)):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize(
    "sizes", [[10, 0, 150, 3], [0, 0, 0, 0], [384, 0, 0, 0], [100, 20, 0, 1], [0, 128, 128, 0]]
)
def test_moe_gmm_matches_per_group_einsum(sizes):
    """Forward, input gradient and weight gradient, in interpret mode, for
    ragged groups, empty groups and no rows at all."""
    m, k, n = 3 * ROW_TILE, 24, 40
    sizes = np.asarray(sizes, np.int32)
    ends = np.cumsum(sizes)
    starts, routed = ends - sizes, int(ends[-1])
    x = jax.random.normal(jax.random.key(0), (m, k))
    w = jax.random.normal(jax.random.key(1), (len(sizes), k, n))
    cot = jax.random.normal(jax.random.key(2), (m, n))
    valid = (jnp.arange(m) < routed)[:, None]

    def per_group(x, w):
        return jnp.concatenate(
            [x[a:b] @ w[g] for g, (a, b) in enumerate(zip(starts, ends))]
            + [jnp.zeros((m - routed, n))]
        )

    def loss(fn):
        return lambda x, w: jnp.sum(jnp.where(valid, fn(x, w), 0.0) * cot)

    gmm = lambda x, w: moe_gmm(x, w, jnp.asarray(sizes), True)
    np.testing.assert_allclose(
        np.asarray(gmm(x, w))[:routed], np.asarray(per_group(x, w))[:routed], rtol=1e-5, atol=1e-4
    )
    dx, dw = jax.grad(loss(gmm), (0, 1))(x, w)
    dx_want, dw_want = jax.grad(loss(per_group), (0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(dx)[:routed], np.asarray(dx_want)[:routed], rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_want), rtol=1e-5, atol=1e-4)


def test_moe_gmm_runs_per_agent_under_vmap():
    m, k, n, G = 2 * ROW_TILE, 16, 24, 3
    x = jax.random.normal(jax.random.key(0), (2, m, k))
    w = jax.random.normal(jax.random.key(1), (2, G, k, n))
    sizes = jnp.asarray([[5, 0, 100], [0, 256, 0]], jnp.int32)
    out = jax.vmap(lambda a, b, c: moe_gmm(a, b, c, True))(x, w, sizes)
    for a in range(2):
        want = moe_gmm(x[a], w[a], sizes[a], True)
        routed = int(sizes[a].sum())
        np.testing.assert_array_equal(np.asarray(out[a])[:routed], np.asarray(want)[:routed])


def test_latent_attention_models_refuse_serving():
    b = get_bundle("kanana-2-30b-a3b-smoke")
    params = b.init(jax.random.key(0))
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="MLA decode cache"):
        b.prefill(params, {"tokens": tokens}, 16)


def test_trainer_returns_the_expert_counters():
    from repro.core import DecentralizedTrainer, TrainerConfig, make_topology
    from repro.optim import momentum

    b = get_bundle("kanana-2-30b-a3b-smoke")
    K = 2
    tr = DecentralizedTrainer(
        b.loss_and_stats, b.init, momentum(0.01, 0.9), make_topology("ring", K),
        TrainerConfig(consensus_steps=1),
    )
    state = tr.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (K, 2, 17), 0, b.cfg.vocab)
    _, metrics = tr.local_step(state, {"tokens": tokens}, jax.random.key(2))
    stats = metrics["stats"]["moe"]
    assert stats["routed_slots"].shape == (K, 2)  # agents x MoE layers
    # the smoke config holds every expert: all 2 x 16 x top_k slots are routed here
    np.testing.assert_array_equal(np.asarray(stats["routed_slots"]), 2 * 16 * b.cfg.moe.top_k)
    assert np.all(np.asarray(stats["held_imbalance"]) >= 1.0)
    plain = DecentralizedTrainer(
        b.loss, b.init, momentum(0.01, 0.9), make_topology("ring", K), TrainerConfig(consensus_steps=1)
    )
    assert "stats" not in plain.local_step(state, {"tokens": tokens}, jax.random.key(2))[1]
