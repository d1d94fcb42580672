"""kimi-k2-1t-a32b [moe] — 61L d_model=7168 64H (GQA kv=8) d_ff=2048
vocab=163840, MoE 384 experts top-8 — trillion-parameter MoE (paper-table).
[arXiv:2501.kimi2]

The attention here is a GQA stand-in: the published model has the
DeepSeek-V3 architecture, with multi-head latent attention (kv_lora_rank
512, q_lora_rank 1536).  ``models/mla.py`` has MLA for training
(kanana-2-30b-a3b runs it), but converting this config needs an MLA decode
cache for its serving paths, which does not exist.

d_ff=2048 is the per-expert intermediate width (DeepSeek-V3-style narrow
experts); one shared expert of the same width.  Total ~1.03T params, ~32B
active.  Decentralized-training capacity note (DESIGN.md §4): a 1T model
admits at most K=2 agents on a 256-chip v5e pod (agent axis replicated,
experts sharded over ``data`` x ffn over ``model`` => ~15.7 GB/device bf16);
K>=4 exceeds HBM and requires the 2-pod mesh.  The dry-run reports both.
"""
from repro.models.config import AttnCfg, GroupCfg, LayerCfg, ModelConfig, MoECfg
from repro.models.registry import register


def full() -> ModelConfig:
    return ModelConfig(
        name="kimi-k2-1t-a32b",
        family="moe",
        d_model=7168,
        vocab=163840,
        d_ff=2048,
        attn=AttnCfg(n_heads=64, n_kv_heads=8, head_dim=128, qk_norm=False, rope_theta=5e5),
        moe=MoECfg(
            n_experts=384,
            top_k=8,
            d_ff_expert=2048,
            shared_d_ff=2048,
            capacity_factor=1.25,
            group_size=4096,
        ),
        groups=(GroupCfg(name="main", repeat=61, unit=(LayerCfg("moe"),)),),
        param_dtype="bfloat16",
        num_agents=2,
        expert_axis="data",
        source="arXiv:2501.kimi2",
    )


def reduced() -> ModelConfig:
    return ModelConfig(
        name="kimi-k2-1t-a32b-smoke",
        family="moe",
        d_model=128,
        vocab=512,
        d_ff=64,
        attn=AttnCfg(n_heads=8, n_kv_heads=2, head_dim=16, rope_theta=5e5),
        moe=MoECfg(n_experts=4, top_k=2, d_ff_expert=64, shared_d_ff=64, group_size=64),
        groups=(GroupCfg(name="main", repeat=2, unit=(LayerCfg("moe"),)),),
        param_dtype="float32",
        compute_dtype="float32",
        num_agents=4,
        remat=False,
    )


def gs1024() -> ModelConfig:
    """§Perf variant: dispatch group size 4096 -> 1024.  The GShard dispatch
    tensor scales as T x E x cap with cap ∝ group_size, so smaller groups cut
    the dispatch einsum's FLOPs and bytes ~4x (at somewhat higher drop
    variance — same expected capacity ratio)."""
    import dataclasses

    cfg = full()
    return dataclasses.replace(
        cfg,
        name="kimi-k2-1t-a32b-gs1024",
        moe=dataclasses.replace(cfg.moe, group_size=1024),
    )


register("kimi-k2-1t-a32b", full)
register("kimi-k2-1t-a32b-smoke", reduced)
register("kimi-k2-1t-a32b-gs1024", gs1024)
