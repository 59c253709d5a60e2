"""moonlight-16b-a3b — DeepSeek-V3 block at hidden 2048: MLA without
q-LoRA, 1 dense layer then 64 routed top-6 experts with 2 shared, sigmoid
router with a selection-only bias (noaux_tc), no MTP layer.
[hf:moonshotai/Moonlight-16B-A3B config.json]

The two shared experts are one SwiGLU of width 2 × 1408: the sum of two
SwiGLUs is one SwiGLU over their concatenated hidden units.
``n_group`` = ``topk_group`` = 1, so the group-limited selection is the
plain top-6. ``max_position_embeddings`` 8192 with no rope scaling.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,                   # v head dim
    d_ff=11264,                     # dense FFN width of the first layer
    vocab_size=163840,
    moe=MoEConfig(
        num_experts=64,
        top_k=6,
        d_ff_expert=1408,
        num_shared_experts=2,
        d_ff_shared=1408,
        router="sigmoid",
        routed_scaling=2.446,
        impl="dropless",
    ),
    mla=MLAConfig(
        q_lora_rank=0,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
    ),
    first_k_dense=1,
    mtp_depth=0,
    rope_theta=50000.0,
    norm_eps=1e-5,
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json",
)
