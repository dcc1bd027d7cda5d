"""Operations and bytes of the programs the live RL loop runs, and the
chip peaks they are divided by.

Everything here is worked out from the configuration's shapes and from
counts (tokens, dispatches, program calls), never from the program's own
code, so a later change to the program cannot change the yardstick. Each
function gives the least work the program's call needs: what a perfect
implementation would still have to compute and move. A roofline share is
that least time over the measured device time, so it can only read low,
never above 100%, when a term is left out.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Sequence

# Published peaks per chip, keyed by ``jax.Device.device_kind``.
PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
    # at 819 GB/s per chip
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}

BF16 = 2
F32 = 4


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


@dataclass(frozen=True)
class Shapes:
    """The sizes of a dense Qwen3-style decoder (GQA attention with
    qk-norm, SwiGLU MLP, untied LM head)."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def from_config(cls, cfg: Dict) -> "Shapes":
        """From a Hugging Face style ``config.json`` dict."""
        return cls(layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
                   heads=cfg["num_attention_heads"],
                   kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
                   vocab=cfg["vocab_size"])

    @property
    def layer_matrix_params(self) -> int:
        """q, k, v, o projections and the three MLP matrices of a layer."""
        d, hd = self.d_model, self.head_dim
        attn = 2 * d * self.heads * hd + 2 * d * self.kv_heads * hd
        return attn + 3 * d * self.d_ff

    @property
    def layer_vector_params(self) -> int:
        """The two RMSNorm scales and the q/k norm scales of a layer."""
        return 2 * self.d_model + 2 * self.head_dim

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def total_params(self) -> int:
        per_layer = self.layer_matrix_params + self.layer_vector_params
        return (self.layers * per_layer + self.d_model          # final norm
                + self.head_params + self.vocab * self.d_model)  # embedding

    @property
    def matmul_params(self) -> int:
        """Non-embedding matrix parameters, LM head included: what one
        token's forward pass multiplies by (2 FLOPs each)."""
        return self.layers * self.layer_matrix_params + self.head_params

    @property
    def kv_bytes_per_token(self) -> int:
        """bf16 K and V of one position over all layers."""
        return self.layers * 2 * self.kv_heads * self.head_dim * BF16

    def attention_flops(self, length: int) -> int:
        """Forward FLOPs of causal attention (scores and weighted values)
        over one sequence of ``length`` tokens, all layers."""
        pairs = length * (length + 1) // 2
        return self.layers * 4 * self.heads * self.head_dim * pairs


def decode_work(s: Shapes, dispatches: int, steps_per_dispatch: int,
                tokens: int) -> Dict[str, float]:
    """Decode blocks: every scanned step of every dispatch reads the
    weights once in bf16 (LM head included); each emitted token costs
    2 FLOPs per matrix parameter and writes its K/V. Reading the cached
    K/V of each row is left out: the counters do not give the rows'
    lengths, and at these lengths it is under 3% of the weight bytes."""
    weight_reads = dispatches * steps_per_dispatch
    return {"flops": 2.0 * s.matmul_params * tokens,
            "bytes": (weight_reads * BF16 * s.matmul_params
                      + tokens * s.kv_bytes_per_token)}


def prefill_work(s: Shapes, calls: int, tokens: int) -> Dict[str, float]:
    """Admission prefills: ``tokens`` real prompt tokens (bucket padding
    excluded) through the layers, the LM head at one position per call,
    the weights read once per call in bf16, and the prompt's K/V written.
    Attention over the prompt is left out (under 0.2% of the FLOPs at
    prompts of at most 416 tokens)."""
    flops = 2.0 * (s.layers * s.layer_matrix_params * tokens
                   + s.head_params * calls)
    return {"flops": flops,
            "bytes": (calls * BF16 * s.matmul_params
                      + tokens * s.kv_bytes_per_token)}


def train_work(s: Shapes, lengths: Sequence[int],
               steps: int = 1) -> Dict[str, float]:
    """GRPO steps over rows of the given real lengths (padding excluded):
    forward and backward are 6 FLOPs per matrix parameter per token plus
    three times the forward attention. The step must at least read and
    write the f32 params and both AdamW moments once: 24 bytes per
    parameter, embedding included. ``lengths`` holds every row of all
    ``steps`` steps."""
    tokens = sum(lengths)
    flops = (6.0 * s.matmul_params * tokens
             + 3.0 * sum(s.attention_flops(n) for n in lengths))
    return {"flops": flops, "bytes": steps * 6 * F32 * s.total_params}


def least_seconds(work: Dict[str, float], peak: Dict[str, float]) -> float:
    """The larger of operations over peak FLOP/s and bytes over HBM
    bandwidth."""
    return max(work["flops"] / peak["flops"],
               work["bytes"] / peak["hbm_bytes_per_s"])
