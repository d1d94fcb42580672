"""Decoder-only LM agents (h2o-danube-3-4b's layer: RMSNorm, GQA attention
with RoPE over a sliding window, a SiLU-gated MLP): the binding of the
program (``repro.models`` decoder engine) to the benchmark, the weights and
tokens the benchmark makes from the seed, and the model's FLOP count.

The weights are the benchmark's own, made on the device from the seed in
one jitted call, in the program's parameter layout; the plain reference
(``bench/reference/danube.py``) starts from the same weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def model_config(cfg):
    from repro.models.config import AttnCfg, GroupCfg, LayerCfg, ModelConfig

    return ModelConfig(
        name="danube-bench",
        family="dense",
        d_model=cfg["d_model"],
        vocab=cfg["vocab"],
        d_ff=cfg["d_ff"],
        attn=AttnCfg(
            n_heads=cfg["n_heads"], n_kv_heads=cfg["n_kv_heads"],
            head_dim=cfg["head_dim"], qk_norm=False, rope_theta=cfg["rope_theta"],
        ),
        groups=(
            GroupCfg(name="main", repeat=cfg["n_layers"],
                     unit=(LayerCfg("attn_mlp", window=cfg["window"]),)),
        ),
        tie_embeddings=cfg["tie_embeddings"],
        norm_eps=cfg["norm_eps"],
        param_dtype=cfg["param_dtype"],
        compute_dtype=cfg["compute_dtype"],
        remat=cfg["remat"],
        num_agents=cfg["num_agents"],
    )


def loss_fn(cfg):
    from repro.models.registry import build_bundle

    return build_bundle(model_config(cfg)).loss


def program_init(cfg):
    from repro.models.registry import build_bundle

    return build_bundle(model_config(cfg)).init


def init_params(cfg):
    """``key -> params`` of one agent: embeddings N(0, 0.02), projections
    N(0, 1/fan_in), the attention output N(0, 1/(heads x head size)), norm
    gains at zero (the norm scales by 1 + gain)."""
    d, f, V, n = cfg["d_model"], cfg["d_ff"], cfg["vocab"], cfg["n_layers"]
    H, G, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    dt = jnp.dtype(cfg["param_dtype"])

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(dt)

    def init(key):
        k = jax.random.split(key, 9)
        return {
            "embed": {"tok": (0.02 * jax.random.normal(k[0], (V, d))).astype(dt)},
            "main_blocks": {
                "ln1": jnp.zeros((n, d), dt),
                "ln2": jnp.zeros((n, d), dt),
                "attn": {
                    "wq": normal(k[1], (n, d, H, hd), d),
                    "wk": normal(k[2], (n, d, G, hd), d),
                    "wv": normal(k[3], (n, d, G, hd), d),
                    "wo": normal(k[4], (n, H, hd, d), H * hd),
                },
                "mlp": {
                    "w_gate": normal(k[5], (n, d, f), d),
                    "w_up": normal(k[6], (n, d, f), d),
                    "w_down": normal(k[7], (n, f, d), f),
                },
            },
            "final_norm": {"w": jnp.zeros((d,), dt)},
            "lm_head": {"w": normal(k[8], (d, V), d)},
        }

    return init


def select(cfg, traffic):
    """``(data, key) -> batches``: each agent's sequences for one call,
    ``seq_len + 1`` tokens drawn uniformly from the vocabulary, as leading
    ``(L, K, B)`` axes."""
    shape = (traffic["local_steps"], cfg["num_agents"], cfg["batch_size"], cfg["seq_len"] + 1)

    def pick(data, key):
        return {"tokens": jax.random.randint(key, shape, 0, cfg["vocab"], jnp.int32)}

    return pick


def samples_per_call(cfg, traffic) -> int:
    """Sequences trained on by all agents in one call."""
    return traffic["local_steps"] * cfg["num_agents"] * cfg["batch_size"]


def flops_per_sample(cfg) -> float:
    """Model FLOPs of one training sequence: forward and backward, 3 x the
    forward's 2 x MACs of every matmul weight per token, plus causal
    attention's scores and values over the keys each position sees within
    the window.  Remat's recompute is not counted."""
    d, f, V, n, T = cfg["d_model"], cfg["d_ff"], cfg["vocab"], cfg["n_layers"], cfg["seq_len"]
    H, G, hd, W = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"], cfg["window"]
    layer = d * hd * (2 * H + 2 * G) + 3 * d * f
    keys_seen = sum(min(i, W) for i in range(1, T + 1))  # over the T positions
    forward = 2 * T * (n * layer + d * V) + n * 4 * H * hd * keys_seen
    return 3.0 * forward
