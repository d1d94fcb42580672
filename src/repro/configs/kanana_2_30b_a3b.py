"""kanana-2-30b-a3b [moe] — 48L d_model=2048, DeepSeek-V3 block: multi-head
latent attention (32 heads, no q compression, kv_lora_rank 512, head dims
128 nope + 64 rope for queries/keys and 128 for values, interleaved RoPE),
one leading dense layer of width 6144, then 47 layers of 128 routed experts
of width 768 (sigmoid scores, top-6, weights normalised and scaled by
2.448) with 2 shared experts; vocab 128,256, untied embeddings, context
32,768.  Source: https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601
(config.json, model_type deepseek_v3); equations in DeepSeek-V2
(arXiv:2405.04434) and DeepSeek-V3 (arXiv:2412.19437, §2.1).

Departures: the two shared experts are one SiLU-gated FFN of width 1,536
(the same function); noaux_tc's selection bias (``e_score_correction_bias``)
is left out, so selection uses the scores alone, since its update is a
per-step load controller and the trainer has no path for one; the balance
loss weight alpha = 1e-4 is assumed (DeepSeek-V3 §4.2); norm gains are
parametrised as 1 + g.  Training only: an MLA decode cache does not exist,
so prefill/decode refuse the model.
"""
from repro.models.config import GroupCfg, LayerCfg, MLACfg, ModelConfig, MoECfg
from repro.models.registry import register

SOURCE = "https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601"


def full() -> ModelConfig:
    return ModelConfig(
        name="kanana-2-30b-a3b",
        family="moe",
        d_model=2048,
        vocab=128256,
        d_ff=6144,
        mla=MLACfg(
            n_heads=32, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, rope_theta=1e6,
        ),
        moe=MoECfg(
            n_experts=128, top_k=6, d_ff_expert=768, shared_d_ff=2 * 768,
            routed_scale=2.448, aux_loss_weight=1e-4,
        ),
        groups=(
            GroupCfg(name="dense", repeat=1, unit=(LayerCfg("mla_mlp"),)),
            GroupCfg(name="moe", repeat=47, unit=(LayerCfg("mla_moe"),)),
        ),
        tie_embeddings=False,
        norm_eps=1e-6,
        param_dtype="bfloat16",
        head_chunk=1024,
        num_agents=2,
        source=SOURCE,
    )


def reduced() -> ModelConfig:
    return ModelConfig(
        name="kanana-2-30b-a3b-smoke",
        family="moe",
        d_model=64,
        vocab=256,
        d_ff=128,
        mla=MLACfg(
            n_heads=4, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, rope_theta=1e6,
        ),
        moe=MoECfg(
            n_experts=4, top_k=2, d_ff_expert=32, shared_d_ff=2 * 32,
            routed_scale=2.448, aux_loss_weight=1e-4,
        ),
        groups=(
            GroupCfg(name="dense", repeat=1, unit=(LayerCfg("mla_mlp"),)),
            GroupCfg(name="moe", repeat=2, unit=(LayerCfg("mla_moe"),)),
        ),
        param_dtype="float32",
        compute_dtype="float32",
        num_agents=4,
        remat=False,
    )


register("kanana-2-30b-a3b", full)
register("kanana-2-30b-a3b-smoke", reduced)
