"""mellum2-12b-a2.5b [moe] — 64 experts top-8, window and full layers 3:1.
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct]

Published: 28 layers of hidden size 2304; GQA with 32 query heads and 4
kv heads of 128; three sliding-window layers (window 1024) to each full
layer; every MLP sparse — 64 routed experts of width 896, top-8, a softmax
router with ``norm_topk_prob``, no shared expert; vocabulary 98,304,
untied; RMSNorm eps 1e-6.  12.15 G parameters, 2.44 G active a token.

Under the paper's rewrite (``attn_mode='aaren'``, DESIGN.md §4) the
sliding and the full layers alike become Aaren layers, with no RoPE: the
published RoPE (theta 500,000) and its YaRN extension of the full layers
do not apply, and the window does not bound an Aaren layer.  With
``attn_mode='softmax'`` the layers keep their published kinds.  The
multi-token-prediction head that the model card mentions is not in the
published config and is not built.
"""

from repro.configs.base import ArchConfig, register


@register
def mellum2_12b_a2p5b() -> ArchConfig:
    return ArchConfig(
        name="mellum2-12b-a2.5b",
        family="moe",
        n_layers=28,
        d_model=2304,
        n_heads=32,
        n_kv_heads=4,
        head_dim=128,
        d_ff=7168,         # the published dense width; no layer is dense
        vocab=98304,
        pattern=("attn_local",) * 3 + ("attn",),
        mlp_pattern=("moe",) * 4,
        window=1024,
        n_experts=64,
        n_experts_per_tok=8,
        moe_d_ff=896,
        rope_theta=500000.0,
        norm="rmsnorm",
        tie_embeddings=False,
        optimizer="adamw",
        remat="block",
    )
