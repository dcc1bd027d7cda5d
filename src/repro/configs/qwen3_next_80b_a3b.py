"""qwen3-next-80b-a3b — Gated DeltaNet and gated attention in a 3:1
period, a sparse MoE (512 experts, top-10, one gated shared expert) on
every layer. [hf:Qwen/Qwen3-Next-80B-A3B-Instruct]"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-next-80b-a3b", family="hybrid",
    source="hf:Qwen/Qwen3-Next-80B-A3B-Instruct (48L d=2048 16H kv=2 "
           "hd=256 rot=0.25, linear 16k/32v x 128 conv 4, 512e top-10 "
           "moe_ff=512 + shared 512, v=151936)",
    num_layers=48, d_model=2048, num_heads=16, num_kv_heads=2, head_dim=256,
    d_ff=5120, vocab_size=151936, rope_theta=10000000.0,
    block_pattern=(("gdn", "moe"),) * 3 + (("attn", "moe"),),
    qk_norm=True, attn_output_gate=True, partial_rotary_factor=0.25,
    zero_centered_norm=True,
    linear_num_key_heads=16, linear_num_value_heads=32,
    linear_key_head_dim=128, linear_value_head_dim=128,
    linear_conv_kernel_dim=4,
    num_experts=512, top_k=10, moe_d_ff=512, num_shared_experts=1,
    shared_expert_gate=True,
    # a chip's share cannot form the global load-balance term
    router_aux_coef=0.0, router_z_coef=0.0,
)

# The live RL loop on ONE TPU v5e chip: every width stays. Cuts: depth 48
# -> 4 (one whole period), chip 0's 8 of each layer's 512 experts (a
# 64-chip expert-parallel deployment; the router keeps all 512 and its
# top-10), vocab 151,936 -> 18,992 rows (1/8). 323,677,248 params. With 16
# experts (424.3 M) the GRPO step compiled to 14.5 GiB of the compiler's
# 15.75 with the engine's f32 copy, but the float32 reference that decides
# the benchmark's `correct` ran out of the chip's memory after the window.
CHIP_SHARE = CONFIG.with_(name="qwen3-next-80b-a3b-chip-share",
                          num_layers=4, num_experts=8, router_experts=512,
                          expert_offset=0,
                          vocab_size=CONFIG.vocab_size // 8)
