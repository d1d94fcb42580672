"""Assigned-architecture configs.  Importing this package registers every
arch (full + reduced smoke variant) in the model registry."""
from repro.configs import (  # noqa: F401
    falcon_mamba_7b,
    gemma3_27b,
    h2o_danube_3_4b,
    hymba_1_5b,
    kanana_2_30b_a3b,
    kimi_k2_1t_a32b,
    llama4_maverick_400b_a17b,
    llava_next_34b,
    qwen3_4b,
    qwen3_8b,
    whisper_large_v3,
)
from repro.configs.shapes import (
    SHAPES,
    TRAIN_4K,
    PREFILL_32K,
    DECODE_32K,
    LONG_500K,
    InputShape,
    applicable,
)
from repro.configs.resnet20_cifar import PAPER, PaperExperimentConfig, TOPOLOGIES

ASSIGNED_ARCHS = (
    "llava-next-34b",
    "hymba-1.5b",
    "llama4-maverick-400b-a17b",
    "qwen3-8b",
    "h2o-danube-3-4b",
    "kimi-k2-1t-a32b",
    "whisper-large-v3",
    "falcon-mamba-7b",
    "qwen3-4b",
    "gemma3-27b",
    "kanana-2-30b-a3b",
)
