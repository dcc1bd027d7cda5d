"""Operations and bytes of the live RL loop's programs for a Qwen3-Next
decoder (Gated DeltaNet and gated attention layers, sparse experts) at one
chip's share of experts; the peaks are ``work.PEAKS``.

As in ``work.py``, everything is worked out from the configuration's
shapes and from counts (tokens, dispatches, and the expert counters the
program reports: held experts touched, assignments to held experts), never
from the program's own code, and each function gives the least work a
call needs, so a roofline share can only read low. Left out throughout:
the FLOPs of attention scores and of the delta rule's own products, and
the conv taps, norms and gates (a few percent of the matmul FLOPs at these
lengths; ``work.py`` leaves attention out the same way).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Sequence

from benchmarks.chip.work import BF16, F32, least_seconds  # noqa: F401


@dataclass(frozen=True)
class Shapes:
    """The sizes of a Qwen3-Next decoder as a configuration file gives
    them; ``experts`` is how many this chip holds."""
    layers: int
    interval: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    lin_k_heads: int
    lin_v_heads: int
    lin_k_dim: int
    lin_v_dim: int
    conv: int
    experts: int
    router_experts: int
    expert_ff: int
    shared_ff: int
    vocab: int

    @classmethod
    def from_config(cls, cfg: Dict) -> "Shapes":
        return cls(layers=cfg["num_hidden_layers"],
                   interval=cfg["full_attention_interval"],
                   d_model=cfg["hidden_size"],
                   heads=cfg["num_attention_heads"],
                   kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg["head_dim"],
                   lin_k_heads=cfg["linear_num_key_heads"],
                   lin_v_heads=cfg["linear_num_value_heads"],
                   lin_k_dim=cfg["linear_key_head_dim"],
                   lin_v_dim=cfg["linear_value_head_dim"],
                   conv=cfg["linear_conv_kernel_dim"],
                   experts=cfg["num_experts"],
                   router_experts=cfg["deployment"]["router_experts"],
                   expert_ff=cfg["moe_intermediate_size"],
                   shared_ff=cfg["shared_expert_intermediate_size"],
                   vocab=cfg["vocab_size"])

    @property
    def attn_layers(self) -> int:
        return self.layers // self.interval

    @property
    def linear_layers(self) -> int:
        return self.layers - self.attn_layers

    @property
    def linear_matrix_params(self) -> int:
        """q, k, v, z, b, a and output projections of a DeltaNet layer."""
        d = self.d_model
        kd = self.lin_k_heads * self.lin_k_dim
        vd = self.lin_v_heads * self.lin_v_dim
        return d * (2 * kd + 2 * vd + 2 * self.lin_v_heads) + vd * d

    @property
    def attn_matrix_params(self) -> int:
        """q, output gate, k, v and o projections of an attention layer."""
        d, hd = self.d_model, self.head_dim
        return 3 * d * self.heads * hd + 2 * d * self.kv_heads * hd

    @property
    def moe_dense_params(self) -> int:
        """Router, shared expert and its gate: what every token of an
        expert layer multiplies by."""
        d = self.d_model
        return d * self.router_experts + 3 * d * self.shared_ff + d

    @property
    def expert_params(self) -> int:
        """One routed expert's three matrices."""
        return 3 * self.d_model * self.expert_ff

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def layer_dense_params(self) -> int:
        """Every layer's matrix parameters but the routed experts'."""
        return (self.linear_layers * self.linear_matrix_params
                + self.attn_layers * self.attn_matrix_params
                + self.layers * self.moe_dense_params)

    @property
    def dense_matmul_params(self) -> int:
        """Matrix parameters a token always multiplies by, LM head
        included: 2 FLOPs each per token."""
        return self.layer_dense_params + self.head_params

    @property
    def total_params(self) -> int:
        d = self.d_model
        vd = self.lin_v_heads * self.lin_v_dim
        conv_dim = 2 * self.lin_k_heads * self.lin_k_dim + vd
        linear_vectors = conv_dim * self.conv + 2 * self.lin_v_heads \
            + self.lin_v_dim
        return (self.dense_matmul_params + self.vocab * d
                + self.layers * self.experts * self.expert_params
                + self.linear_layers * linear_vectors
                + self.attn_layers * 2 * self.head_dim
                + (2 * self.layers + 1) * d)

    @property
    def state_bytes(self) -> int:
        """One row's float32 DeltaNet state in one layer."""
        return self.lin_v_heads * self.lin_k_dim * self.lin_v_dim * F32

    @property
    def kv_bytes_per_token(self) -> int:
        """bf16 K and V of one position over the attention layers."""
        return self.attn_layers * 2 * self.kv_heads * self.head_dim * BF16


def decode_work(s: Shapes, dispatches: int, steps_per_dispatch: int,
                tokens: int, touched: int,
                assignments: int) -> Dict[str, float]:
    """Decode blocks. Each scanned step reads, in bf16, every weight but
    the routed experts' once (LM head included) and each held expert some
    row routed to once (``touched``, summed over steps and layers); each
    emitted token reads and writes its row's float32 state in every
    DeltaNet layer and writes its K/V in the attention layers. FLOPs: 2 per
    dense matrix parameter a token, 2 per expert parameter an assignment
    to a held expert (``assignments``)."""
    steps = dispatches * steps_per_dispatch
    flops = (2.0 * s.dense_matmul_params * tokens
             + 2.0 * s.expert_params * assignments)
    return {"flops": flops,
            "bytes": (steps * BF16 * s.dense_matmul_params
                      + touched * BF16 * s.expert_params
                      + tokens * (2 * s.linear_layers * s.state_bytes
                                  + s.kv_bytes_per_token))}


def prefill_flops(s: Shapes, tokens: int) -> float:
    """Admission prefills: 2 FLOPs per layer matrix parameter but the
    routed experts' per real prompt token (the counters hold no prefill
    assignments, so the experts' part is left out)."""
    return 2.0 * s.layer_dense_params * tokens


def prefill_work(s: Shapes, calls: int, tokens: int) -> Dict[str, float]:
    """Admission prefills (the engine's ``_prefill_into_slot``):
    ``prefill_flops`` for the real prompt tokens (bucket padding
    excluded) and the LM head at one position a call. Bytes: a call reads
    every weight but the routed experts' once in bf16 (it may touch any
    held expert, but the counters do not say which) and writes its row's
    float32 state in every DeltaNet layer; each token writes its K/V."""
    return {"flops": (prefill_flops(s, tokens)
                      + 2.0 * s.head_params * calls),
            "bytes": (calls * (BF16 * s.dense_matmul_params
                               + s.linear_layers * s.state_bytes)
                      + tokens * s.kv_bytes_per_token)}


def train_work(s: Shapes, lengths: Sequence[int], steps: int,
               assignments: int, padded_tokens: int) -> Dict[str, float]:
    """GRPO steps over rows of the given real lengths: 6 FLOPs per dense
    matrix parameter per real token, plus 6 per expert parameter per
    assignment to a held expert. The step's counter holds the assignments
    of every position of the padded rows (``padded_tokens``), so they are
    taken in the real tokens' share; the expert part is about 1% of the
    FLOPs. Bytes: the float32 params and both AdamW moments read and
    written once, 24 per parameter."""
    tokens = sum(lengths)
    here = assignments * tokens / padded_tokens if padded_tokens else 0.0
    flops = 6.0 * s.dense_matmul_params * tokens \
        + 6.0 * s.expert_params * here
    return {"flops": flops, "bytes": steps * 6 * F32 * s.total_params}
