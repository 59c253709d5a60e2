"""Operations and bytes of the LLM cells' work, from the configuration's
shapes alone (bench/configs/<config>.json keys), so that no change to the
program can change what it is credited with. A multiply-add counts as two
operations; operands are bfloat16, as the configuration computes.
"""
from __future__ import annotations

BF16 = 2


def moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def expected_rows_per_token(cfg: dict) -> float:
    """Rows the held experts take per token on average: top-k of the
    router's experts, of which the held share."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / \
        cfg["deployment"]["router_experts"]


def forward_per_token(cfg: dict) -> float:
    """Model operations of one token's forward pass through the share: MLA
    projections, causal attention at S²/2 of the sequence, the dense MLP,
    the router, the shared experts and the held experts' expected rows, and
    the head over the vocabulary slice."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    rank, v = cfg["kv_lora_rank"], cfg["v_head_dim"]
    S, L = cfg["train"]["seq"], cfg["num_hidden_layers"]
    f = cfg["moe_intermediate_size"]
    proj = 2 * d * H * qk + 2 * d * (rank + cfg["qk_rope_head_dim"]) \
        + 2 * rank * H * (cfg["qk_nope_head_dim"] + v) + 2 * H * v * d
    core = H * (qk + v) * S                     # 2·(S²/2)·H·(qk + v) / S
    dense = 6 * d * cfg["intermediate_size"]
    moe = 2 * d * cfg["deployment"]["router_experts"] \
        + 6 * d * cfg["n_shared_experts"] * f \
        + expected_rows_per_token(cfg) * 6 * d * f
    return float(L * (proj + core) + cfg["first_k_dense_replace"] * dense
                 + moe_layers(cfg) * moe + 2 * d * cfg["vocab_size"])


def train_per_sequence(cfg: dict) -> float:
    """Forward and backward (twice the forward) of one sequence; the
    recomputation of the forward is not counted."""
    return 3.0 * forward_per_token(cfg) * cfg["train"]["seq"]


def tokens_per_step(cfg: dict, traffic: dict) -> int:
    return traffic["sequences"] * cfg["train"]["seq"]


GMM_PASSES = 4      # forward, its recomputation, input and weight gradients


def grouped_matmuls(cfg: dict, rows: float, layer_steps: float):
    """(flops, bytes) the held experts' grouped products take for `rows`
    rows over `layer_steps` MoE layer-steps of training: each pass reads a
    row and the held experts' SwiGLU weights and writes the products (gate
    and up from d to 2f, down from f to d)."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = GMM_PASSES * rows * 6.0 * d * f
    nbytes = GMM_PASSES * BF16 * (rows * (2 * d + 3 * f) + layer_steps * 3
                                  * cfg["n_routed_experts"] * d * f)
    return flops, nbytes


def gmm_calls_per_layer_step() -> int:
    """Kernel launches a layer-step needs: two products per pass."""
    return 2 * GMM_PASSES
