"""Decoder-only LM agents with kanana-2-30b-a3b's DeepSeek-V3 block: latent
attention, a leading dense layer, then layers of sigmoid-routed experts of
which this chip holds a share, with shared experts.  The binding of the
program (``repro.models`` decoder engine, ``mla_mlp`` and ``mla_moe``
layers) to the benchmark, the weights and tokens the benchmark makes from
the seed, and the model's FLOP count.

The configuration's file keeps the source's keys (``config.json`` of the
published model); ``n_routed_experts`` there is the experts this chip holds
and ``router_experts`` the router's published width.  Each layer is a group
of its own (``layer<i>_blocks``, one slot): under the trainer's ``vmap``
over agents a scan over a stacked group first lays its weights out
layer-major, a copy of all of them beside the state, and the float32
reference's copy does not fit on the chip beside its agents' parameters,
momentum and gradients.  The weights are the
benchmark's own, made on the device from the seed in one jitted call, in
the program's parameter layout; the plain reference
(``bench/reference/kanana.py``) starts from the same weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HEAD_CHUNK = 1024  # positions per block of the head and cross-entropy


def model_config(cfg):
    from repro.models.config import GroupCfg, LayerCfg, MLACfg, ModelConfig, MoECfg

    n_dense = cfg["first_k_dense_replace"]
    return ModelConfig(
        name="kanana-bench",
        family="moe",
        d_model=cfg["hidden_size"],
        vocab=cfg["vocab_size"],
        d_ff=cfg["intermediate_size"],
        mla=MLACfg(
            n_heads=cfg["num_attention_heads"], kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"], qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        ),
        moe=MoECfg(
            n_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
            d_ff_expert=cfg["moe_intermediate_size"],
            shared_d_ff=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            aux_loss_weight=cfg["aux_loss_weight"],
            routed_scale=cfg["routed_scaling_factor"], n_held=cfg["n_routed_experts"],
            held_offset=cfg["held_offset"],
        ),
        groups=tuple(
            GroupCfg(name=f"layer{i}", repeat=1,
                     unit=(LayerCfg("mla_mlp" if i < n_dense else "mla_moe"),))
            for i in range(cfg["num_hidden_layers"])
        ),
        tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"],
        param_dtype=cfg["param_dtype"],
        compute_dtype=cfg["compute_dtype"],
        remat=cfg["remat"],
        head_chunk=HEAD_CHUNK,
        num_agents=cfg["num_agents"],
    )


def loss_fn(cfg):
    """The program's loss with the expert layers' counters as stats."""
    from repro.models.registry import build_bundle

    return build_bundle(model_config(cfg)).loss_and_stats


def program_init(cfg):
    from repro.models.registry import build_bundle

    return build_bundle(model_config(cfg)).init


def init_params(cfg):
    """``key -> params`` of one agent, one group ``layer<i>_blocks`` of one
    slot per layer: embeddings N(0, 0.02), projections N(0, 1/fan_in), the
    attention output N(0, 1/(heads x value size)), norm gains at zero (the
    norm scales by 1 + gain)."""
    d, V, f, fe = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"]
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    n_dense, n_layers = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    G, E, fs = cfg["n_routed_experts"], cfg["router_experts"], cfg["n_shared_experts"] * fe
    dt = jnp.dtype(cfg["param_dtype"])

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(dt)

    def attn(key, n):
        k = jax.random.split(key, 4)
        return {
            "wq": normal(k[0], (n, d, H, dn + dr), d),
            "wkv_a": normal(k[1], (n, d, r + dr), d),
            "kv_norm": jnp.zeros((n, r), dt),
            "wkv_b": normal(k[2], (n, r, H, dn + dv), r),
            "wo": normal(k[3], (n, H, dv, d), H * dv),
        }

    def layer(key, i):
        k = jax.random.split(key, 8)
        if i < n_dense:
            ffn = {"mlp": {
                "w_gate": normal(k[1], (1, d, f), d),
                "w_up": normal(k[2], (1, d, f), d),
                "w_down": normal(k[3], (1, f, d), f),
            }}
        else:
            ffn = {"moe": {
                "router": normal(k[1], (1, d, E), d),
                "we_gate": normal(k[2], (1, G, d, fe), d),
                "we_up": normal(k[3], (1, G, d, fe), d),
                "we_down": normal(k[4], (1, G, fe, d), fe),
                "ws_gate": normal(k[5], (1, d, fs), d),
                "ws_up": normal(k[6], (1, d, fs), d),
                "ws_down": normal(k[7], (1, fs, d), fs),
            }}
        return {"ln1": jnp.zeros((1, d), dt), "ln2": jnp.zeros((1, d), dt), "attn": attn(k[0], 1), **ffn}

    def init(key):
        k = jax.random.split(key, n_layers + 2)
        return {
            "embed": {"tok": (0.02 * jax.random.normal(k[0], (V, d))).astype(dt)},
            **{f"layer{i}_blocks": layer(k[2 + i], i) for i in range(n_layers)},
            "final_norm": {"w": jnp.zeros((d,), dt)},
            "lm_head": {"w": normal(k[1], (d, V), d)},
        }

    return init


def select(cfg, traffic):
    """``(data, key) -> batches``: each agent's sequences for one call,
    ``seq_len + 1`` tokens drawn uniformly from the vocabulary slice, as
    leading ``(L, K, B)`` axes."""
    shape = (traffic["local_steps"], cfg["num_agents"], cfg["batch_size"], cfg["seq_len"] + 1)

    def pick(data, key):
        return {"tokens": jax.random.randint(key, shape, 0, cfg["vocab_size"], jnp.int32)}

    return pick


def samples_per_call(cfg, traffic) -> int:
    """Sequences trained on by all agents in one call."""
    return traffic["local_steps"] * cfg["num_agents"] * cfg["batch_size"]


def routed_pairs_per_token(cfg) -> float:
    """Token slots per token that reach a held expert at balanced routing:
    ``top_k x held / router width``."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["router_experts"]


def flops_per_sample(cfg) -> float:
    """Model FLOPs of one training sequence: forward and backward, 3 x the
    forward's 2 x MACs of every matmul weight per token (the held experts'
    at balanced routing), plus causal attention's scores and values over
    the keys each position sees.  Remat's recompute is not counted."""
    d, V, T = cfg["hidden_size"], cfg["vocab_size"], cfg["seq_len"]
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    fe = cfg["moe_intermediate_size"]
    mla = d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d
    dense = mla + 3 * d * cfg["intermediate_size"]
    moe = (
        mla + d * cfg["router_experts"] + 3 * d * cfg["n_shared_experts"] * fe
        + routed_pairs_per_token(cfg) * 3 * d * fe
    )
    keys_seen = (T + 1) / 2  # mean over the T positions
    layers = n_dense + n_moe
    forward = 2 * T * (n_dense * dense + n_moe * moe + d * V) + 2 * T * layers * H * (dn + dr + dv) * keys_seen
    return 3.0 * forward
