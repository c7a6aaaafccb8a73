"""Config registry: the 10 assigned architectures + the paper's own scale."""

from repro.configs import (  # noqa: F401  (import for registration)
    aaren_paper,
    dbrx_132b,
    gemma3_27b,
    llama3_405b,
    mellum2_12b_a2p5b,
    mamba2_1p3b,
    minitron_8b,
    phi3_mini_3p8b,
    phi_3_vision_4p2b,
    qwen3_moe_30b_a3b,
    recurrentgemma_9b,
    whisper_medium,
)
from repro.configs.base import (  # noqa: F401
    SHAPES,
    ArchConfig,
    ShapeConfig,
    get_config,
    list_configs,
)
from repro.configs.smoke import smoke_config  # noqa: F401

# The assigned pool (the dry-run iterates these x SHAPES).
ALL_ARCHS = (
    "llama3-405b",
    "gemma3-27b",
    "phi3-mini-3.8b",
    "minitron-8b",
    "recurrentgemma-9b",
    "dbrx-132b",
    "qwen3-moe-30b-a3b",
    "whisper-medium",
    "phi-3-vision-4.2b",
    "mamba2-1.3b",
)
