"""qwen3-8b — the paper's main evaluation model (RollArt Sec. 7)."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-8b", family="dense",
    source="hf:Qwen/Qwen3-8B (36L d=4096 32H kv=8 ff=12288 v=151936)",
    num_layers=36, d_model=4096, num_heads=32, num_kv_heads=8, head_dim=128,
    d_ff=12288, vocab_size=151936, qk_norm=True, rope_theta=1000000.0,
    block_pattern=(("attn", "mlp"),),
)

# The live RL loop on ONE TPU v5e chip (16 GB HBM; the compiler allows
# 15.75 GiB). Every width stays: d_model 4096, 32 q / 8 kv heads of 128,
# d_ff 12288, qk-norm, rope_theta 1e6, bf16 compute over f32 params.
# Two cuts:
#   - depth 36 -> 1 layer, one whole period of the (attn, mlp) pattern;
#   - vocab 151,936 -> 18,992 rows (1/8); the byte tokenizer's ids
#     (data/tokenizer.py, < 259) fit inside it.
# Memory reckoning (GRPO step compiled un-donated for v5e, batch 8 x seq
# 640, f32 params + AdamW moments): 1 layer is 348.5 M params, arguments
# 3.9 GiB + outputs 3.9 GiB + temporaries 0.15 GiB ~ 8 GiB, and the
# engine's own f32 params copy after the first weight sync adds 1.3 GiB.
# 2 layers (541.5 M) need 6.05 + 6.05 + 1.79 ~ 13.9 GiB before the
# engine's 2.0 GiB copy, over the budget; 1 layer with the full vocab
# needs 19.67 GiB. The train state is not donated: the weight push reads
# the params on its own thread after the step returns.
CHIP_SMOKE = CONFIG.with_(name="qwen3-8b-chip-smoke", num_layers=1,
                          vocab_size=CONFIG.vocab_size // 8)
