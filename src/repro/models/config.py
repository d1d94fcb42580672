"""Model configuration schema.

A model is a sequence of scanned *groups*; each group repeats a *unit* of one
or more sub-layers.  Units let us express periodic layer patterns (e.g.
gemma-3's 5 local : 1 global attention) inside a single ``lax.scan`` — every
scan step must trace the same program, so the window sizes are static per
sub-layer and the pattern is encoded structurally.

DRT layer granularity: each scan step of each group is one DRT "layer"
(plus one layer each for embed / final norm / head).  For patterned archs a
DRT layer is therefore one pattern unit — documented in DESIGN.md.
"""
from __future__ import annotations

import dataclasses
from typing import Literal

import jax.numpy as jnp

Family = Literal["dense", "moe", "ssm", "hybrid", "vlm", "audio"]


@dataclasses.dataclass(frozen=True)
class AttnCfg:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    rope_theta: float = 1e6


@dataclasses.dataclass(frozen=True)
class MLACfg:
    """Multi-head latent attention (DeepSeek-V2/V3) without query
    compression: per-head queries of ``qk_nope_head_dim + qk_rope_head_dim``,
    keys and values from a ``kv_lora_rank`` latent plus one shared RoPE key,
    values of ``v_head_dim``."""

    n_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 1e6


@dataclasses.dataclass(frozen=True)
class MoECfg:
    n_experts: int  # the router's width
    top_k: int
    d_ff_expert: int
    shared_d_ff: int = 0  # 0 = no shared expert
    capacity_factor: float = 1.25
    group_size: int = 4096  # tokens per dispatch group
    aux_loss_weight: float = 0.01
    # the expert-share layer ("mla_moe"): the routed weights' scale, and the
    # experts this layer holds, [held_offset, held_offset + n_held) (None:
    # all of them)
    routed_scale: float = 1.0
    n_held: int | None = None
    held_offset: int = 0

    @property
    def held(self) -> int:
        return self.n_experts if self.n_held is None else self.n_held


@dataclasses.dataclass(frozen=True)
class SSMCfg:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int | None = None  # None -> ceil(d_model / 16)

    def resolve_dt_rank(self, d_model: int) -> int:
        return self.dt_rank if self.dt_rank is not None else -(-d_model // 16)


@dataclasses.dataclass(frozen=True)
class LayerCfg:
    # "mla_mlp" / "mla_moe": latent attention with a dense MLP / with the
    # expert-share layer (models.moe.expert_share_apply)
    kind: Literal["attn_mlp", "moe", "mamba", "hymba", "mla_mlp", "mla_moe"] = "attn_mlp"
    window: int | None = None  # sliding-window size; None = full attention


@dataclasses.dataclass(frozen=True)
class GroupCfg:
    name: str  # parameter key will be f"{name}_blocks"
    repeat: int  # scan length
    unit: tuple[LayerCfg, ...] = (LayerCfg(),)

    @property
    def n_layers(self) -> int:
        return self.repeat * len(self.unit)

    @property
    def param_key(self) -> str:
        return f"{self.name}_blocks"


@dataclasses.dataclass(frozen=True)
class EncoderCfg:
    """Whisper-style encoder (bidirectional) consuming stub frame embeddings."""

    n_layers: int
    n_frames: int  # fixed encoder length (whisper: 1500)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Family
    d_model: int
    vocab: int
    d_ff: int
    groups: tuple[GroupCfg, ...]
    attn: AttnCfg | None = None
    mla: MLACfg | None = None
    moe: MoECfg | None = None
    ssm: SSMCfg | None = None
    encoder: EncoderCfg | None = None  # audio (enc-dec) only
    n_img_tokens: int = 0  # vlm only: stub patch embeddings per image
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: bool = True
    # positions per block of the output head and cross-entropy, each block
    # rematerialised, so that no (B, S, vocab) logits exist at once (long
    # sequences); None: the whole sequence at once
    head_chunk: int | None = None
    # decentralized-training defaults for this arch (see DESIGN.md §4)
    num_agents: int = 16
    expert_axis: str | None = "model"  # mesh axis for the expert dim of MoE weights
    source: str = ""  # citation bracket from the assignment

    @property
    def n_layers(self) -> int:
        return sum(g.n_layers for g in self.groups)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    def param_count(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS = 6·N·D)."""
        d, v = self.d_model, self.vocab
        n = v * d  # embed
        if not self.tie_embeddings:
            n += d * v
        n += d  # final norm
        for g in self.groups:
            per_unit = 0
            for lc in g.unit:
                per_unit += self._layer_params(lc)
            n += g.repeat * per_unit
        if self.encoder is not None:
            a = self.attn
            enc_layer = (
                2 * d  # norms
                + d * a.n_heads * a.head_dim * 2  # wq, wo
                + d * a.n_kv_heads * a.head_dim * 2  # wk, wv
                + (2 if a.qk_norm else 0) * a.head_dim
                + 3 * d * self.d_ff
            )
            n += self.encoder.n_layers * enc_layer + d
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: top_k + shared experts only)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        total = self.param_count()
        n_moe_layers = sum(
            g.repeat * sum(1 for lc in g.unit if lc.kind == "moe") for g in self.groups
        )
        n_share_layers = sum(
            g.repeat * sum(1 for lc in g.unit if lc.kind == "mla_moe") for g in self.groups
        )
        per_expert = 3 * self.d_model * m.d_ff_expert
        total -= n_moe_layers * (m.n_experts - m.top_k) * per_expert
        total -= n_share_layers * (m.held - m.top_k) * per_expert
        return total

    def _layer_params(self, lc: LayerCfg) -> int:
        d = self.d_model
        a = self.attn
        attn_n = 0
        if a is not None:
            attn_n = (
                d * a.n_heads * a.head_dim * 2
                + d * a.n_kv_heads * a.head_dim * 2
                + (2 * a.head_dim if a.qk_norm else 0)
            )
        mlp_n = 3 * d * self.d_ff
        if lc.kind in ("mla_mlp", "mla_moe"):
            a = self.mla
            H, r, rope = a.n_heads, a.kv_lora_rank, a.qk_rope_head_dim
            mla_n = (
                d * H * (a.qk_nope_head_dim + rope)  # wq
                + d * (r + rope)  # wkv_a
                + r  # kv_norm
                + r * H * (a.qk_nope_head_dim + a.v_head_dim)  # wkv_b
                + H * a.v_head_dim * d  # wo
            )
            if lc.kind == "mla_mlp":
                return 2 * d + mla_n + mlp_n
            m = self.moe
            return 2 * d + mla_n + d * m.n_experts + m.held * 3 * d * m.d_ff_expert + 3 * d * m.shared_d_ff
        if lc.kind == "attn_mlp":
            return 2 * d + attn_n + mlp_n
        if lc.kind == "moe":
            m = self.moe
            moe_n = (
                d * m.n_experts
                + m.n_experts * 3 * d * m.d_ff_expert
                + (3 * d * m.shared_d_ff if m.shared_d_ff else 0)
            )
            return 2 * d + attn_n + moe_n
        if lc.kind == "mamba":
            s = self.ssm
            di = s.expand * d
            dtr = s.resolve_dt_rank(d)
            return (
                d  # norm
                + d * 2 * di
                + s.d_conv * di
                + di
                + di * (dtr + 2 * s.d_state)
                + dtr * di
                + di
                + di * s.d_state
                + di
                + di * d
            )
        if lc.kind == "hymba":
            s = self.ssm
            di = s.expand * d
            dtr = s.resolve_dt_rank(d)
            mamba_inner = (
                d * 2 * di
                + s.d_conv * di
                + di
                + di * (dtr + 2 * s.d_state)
                + dtr * di
                + di
                + di * s.d_state
                + di
                + di * d
            )
            return 2 * d + 2 * d + attn_n + mamba_inner + mlp_n
        raise ValueError(lc.kind)
