"""moonlight-16b-a3b — DeepSeek-V3 block: latent attention, sigmoid-routed
experts beside shared experts, one leading dense layer.

[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]
27L, d_model=2048, 16 heads.  MLA without a q latent: q/k width 192
(qk_nope 128 + qk_rope 64, the rope half one key shared by all heads),
v_head_dim 128, kv_lora_rank 512.  Layer 0 is dense (SwiGLU 11264); layers
1-26 route each token to 6 of 64 SwiGLU experts of width 1408 (sigmoid
scores, top-6 of score + e_score_correction_bias, weights the unbiased
scores renormalised times 2.446; n_group = topk_group = 1) and add 2 shared
experts (one SwiGLU of width 2816).  RMSNorm eps 1e-5, rope theta 50000,
vocab 163840 untied, 8192 positions.

Departure: rope pairs follow ``layers.apply_rope`` (halves), where the
checkpoint's rope columns are interleaved; with weights from a seed the
fixed permutation of those columns changes nothing.
"""
from repro.configs.base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,                    # qk_nope_dim + qk_rope_dim
    d_ff=11_264,                     # the leading dense layer
    vocab_size=163_840,
    layer_pattern=("global",),
    mlp="swiglu",
    norm="rmsnorm",
    norm_eps=1e-5,
    rope_theta=50_000.0,
    tie_embeddings=False,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    num_experts=64,
    experts_per_tok=6,
    moe_d_ff=1408,
    first_dense_layers=1,
    num_shared_experts=2,
    router="sigmoid_bias",
    routed_scale=2.446,
    moe_capacity=0.0,                # dropless
    sharding_profile="tp_ep",
    optstate_dtype="bfloat16",
    remat="full",
    source="hf:moonshotai/Moonlight-16B-A3B",
    notes="MLA decode cache of 576 lanes a position; pure full attention "
          "-> long_500k skipped",
))

ENSEMBLE_NOTES = ("Serving member that holds a share of the experts "
                  "(experts_held / expert_rank).")
