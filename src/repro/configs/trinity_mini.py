"""trinity-mini — sparse experts under a 3:1 window/full attention stack.
[hf:arcee-ai/Trinity-Mini, model_type afmoe] 32L d_model=2048 32H (GQA
kv=4) head_dim=128; 2 leading dense layers of width 6144, then 30 expert
layers of 128 routed experts (width 1024, top 8, sigmoid router with a
selection bias, weights normalised and scaled by 2.826) and 1 shared
expert; three sliding-window (2048) layers then one global layer, rotary
on the sliding layers only; QK-norm, a sigmoid output gate on attention,
sandwich norms; vocab 200192, untied embeddings."""
from .base import ModelConfig
from dataclasses import replace

CONFIG = ModelConfig(
    name="trinity-mini", family="moe",
    n_layers=32, d_model=2048, n_heads=32, n_kv_heads=4, d_ff=6144,
    vocab=200192, head_dim=128,
    local_window=2048, global_every=4, nope_global=True,
    qk_norm=True, attn_gate=True, post_norms=True,
    moe_experts=128, moe_top_k=8, moe_d_ff=1024, moe_shared_experts=1,
    moe_router="sigmoid", moe_route_scale=2.826, moe_capacity_factor=-1.0,
    first_dense_layers=2, tie_embed=False, norm_eps=1e-5,
)

SMOKE = replace(
    CONFIG, name="trinity-smoke", n_layers=6, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=96, vocab=256, head_dim=16, local_window=8,
    moe_experts=8, moe_top_k=2, moe_d_ff=32,
)
