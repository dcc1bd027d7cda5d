"""Config system: architecture configs + input shapes.

Every assigned architecture gets a ``src/repro/configs/<id>.py`` exporting
``CONFIG``; the registry here resolves ``--arch <id>`` strings.

Layer structure is expressed as a ``block_pattern``: a tuple of
``(mixer, ffn)`` pairs that tiles the depth (``num_layers % len(pattern) == 0``).
``mixer`` in {"attn", "gdn", "mamba", "rwkv"} ("gdn": Gated DeltaNet linear
attention); ``ffn`` in {"mlp", "moe"}.
The model builder scans over pattern periods so HLO size is depth-independent.
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field
from typing import Optional, Tuple

MIXERS = ("attn", "gdn", "mamba", "rwkv")
FFNS = ("mlp", "moe")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | vlm | audio
    source: str                      # citation for the config
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 64
    d_ff: int = 1024
    vocab_size: int = 4096
    # layer pattern (tiled over depth)
    block_pattern: Tuple[Tuple[str, str], ...] = (("attn", "mlp"),)
    # MoE
    num_experts: int = 0             # experts held here (this chip's share)
    top_k: int = 0
    moe_d_ff: int = 0                # per-expert hidden size (0 -> d_ff)
    num_shared_experts: int = 0
    shared_expert_gate: bool = False  # shared expert scaled by sigmoid(x.w)
    router_experts: int = 0          # experts the router scores (0 -> all
    #                                  held: num_experts)
    expert_offset: int = 0           # router id of the first held expert
    router_aux_coef: float = 0.01
    router_z_coef: float = 1e-3
    # attention details
    qk_norm: bool = False
    attn_output_gate: bool = False   # output * sigmoid(per-head gate proj)
    partial_rotary_factor: float = 1.0  # share of head_dim that is rotated
    zero_centered_norm: bool = False  # every RMSNorm scales by (1 + w), w=0
    rope_theta: float = 500000.0
    sliding_window: Optional[int] = None   # set for long-context variant
    # SSM (mamba) details
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    # RWKV details
    rwkv_head_dim: int = 64
    # Gated DeltaNet (linear attention) details
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # multimodal stub frontend
    cond_len: int = 0                # conditioning prefix length (audio/vlm)
    vision_patches: int = 0          # early-fusion patch embeddings (llama4)
    # numerics
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # misc
    norm_eps: float = 1e-6
    tie_embeddings: bool = False

    def __post_init__(self):
        assert self.num_layers % len(self.block_pattern) == 0, (
            f"{self.name}: num_layers={self.num_layers} not divisible by "
            f"pattern length {len(self.block_pattern)}")
        for mixer, ffn in self.block_pattern:
            assert mixer in MIXERS and ffn in FFNS
        if self.uses_moe:
            assert self.num_experts > 0 and self.top_k > 0
            assert (self.expert_offset + self.num_experts
                    <= self.num_router_experts)

    # ------------------------------------------------------------------
    @property
    def uses_moe(self) -> bool:
        return any(f == "moe" for _, f in self.block_pattern)

    @property
    def uses_attention(self) -> bool:
        return any(m == "attn" for m, _ in self.block_pattern)

    @property
    def attention_free(self) -> bool:
        return not self.uses_attention

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def num_router_experts(self) -> int:
        return self.router_experts or self.num_experts

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def gdn_key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def gdn_value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def gdn_conv_dim(self) -> int:
        """Channels of the causal conv: q, k and v side by side."""
        return 2 * self.gdn_key_dim + self.gdn_value_dim

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def num_rwkv_heads(self) -> int:
        return self.d_model // self.rwkv_head_dim

    @property
    def blocks_per_period(self) -> int:
        return len(self.block_pattern)

    @property
    def num_periods(self) -> int:
        return self.num_layers // len(self.block_pattern)

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ------------------------------------------------------------------
    def reduced(self) -> "ModelConfig":
        """Smoke-test variant: <=2 pattern periods, d_model<=512, <=4 experts."""
        pat = self.block_pattern
        n_layers = len(pat) * min(2, self.num_periods)
        # keep at most one period for long patterns (e.g. jamba's 8)
        if n_layers > 8:
            n_layers = len(pat)
        d_model = min(self.d_model, 256)
        head_dim = 32
        n_heads = max(2, min(4, self.num_heads))
        n_kv = max(1, min(n_heads, self.num_kv_heads))
        if n_heads % n_kv:
            n_kv = 1
        kw = dict(
            name=self.name + "-smoke",
            num_layers=n_layers,
            d_model=d_model,
            num_heads=n_heads,
            num_kv_heads=n_kv,
            head_dim=head_dim,
            d_ff=min(self.d_ff, 512),
            vocab_size=min(self.vocab_size, 512),
            rwkv_head_dim=32,
            mamba_d_state=8,
            cond_len=min(self.cond_len, 4),
            vision_patches=min(self.vision_patches, 4),
            param_dtype="float32",
            dtype="float32",
        )
        if any(m == "gdn" for m, _ in pat):
            kw.update(linear_num_key_heads=2, linear_num_value_heads=4,
                      linear_key_head_dim=32, linear_value_head_dim=32)
        if self.uses_moe:
            kw.update(num_experts=min(4, self.num_experts),
                      top_k=min(2, self.top_k), router_experts=0,
                      expert_offset=0,
                      moe_d_ff=min(self.expert_d_ff, 256))
        if self.sliding_window:
            kw["sliding_window"] = 64
        return self.with_(**kw)

    # ------------------------------------------------------------------
    def param_count(self) -> int:
        """Analytic parameter count (matches the builder's shapes)."""
        d, hd = self.d_model, self.head_dim
        n_attn = sum(m == "attn" for m, _ in self.block_pattern) * self.num_periods
        n_mamba = sum(m == "mamba" for m, _ in self.block_pattern) * self.num_periods
        n_rwkv = sum(m == "rwkv" for m, _ in self.block_pattern) * self.num_periods
        n_gdn = sum(m == "gdn" for m, _ in self.block_pattern) * self.num_periods
        n_moe = sum(f == "moe" for _, f in self.block_pattern) * self.num_periods
        n_mlp = sum(f == "mlp" for _, f in self.block_pattern) * self.num_periods
        p = 0
        # embeddings + head
        p += self.vocab_size * d * (1 if self.tie_embeddings else 2)
        # attention
        q = d * self.num_heads * hd
        kv = d * self.num_kv_heads * hd
        o = self.num_heads * hd * d
        p += n_attn * (q * (2 if self.attn_output_gate else 1) + 2 * kv + o)
        # gated deltanet: q/k/v/z and b/a projections, conv, A_log and
        # dt_bias, gated norm, out_proj
        hv, kd, vd = (self.linear_num_value_heads, self.gdn_key_dim,
                      self.gdn_value_dim)
        p += n_gdn * (d * (2 * kd + 2 * vd + 2 * hv)
                      + self.gdn_conv_dim * self.linear_conv_kernel_dim
                      + 2 * hv + self.linear_value_head_dim + vd * d)
        # mamba
        di, ds = self.mamba_d_inner, self.mamba_d_state
        p += n_mamba * (d * 2 * di            # in_proj (x and z)
                        + di * self.mamba_d_conv
                        + di * (2 * ds + di // 16 + 1)  # x->B,C,dt(lowrank-ish)
                        + di * ds              # A
                        + di * d)              # out_proj
        # rwkv
        p += n_rwkv * (d * d * 5 + d * 64 * 2)  # r,k,v,g,o + decay lora
        # mlp
        p += n_mlp * (3 * d * self.d_ff)
        # moe
        e_ff = self.expert_d_ff
        p += n_moe * (self.num_experts * 3 * d * e_ff
                      + self.num_shared_experts * 3 * d * e_ff
                      + (d if self.shared_expert_gate else 0)
                      + d * self.num_router_experts)
        # norms (negligible)
        p += self.num_layers * 2 * d + d
        return p

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: top_k + shared experts)."""
        if not self.uses_moe:
            return self.param_count()
        d = self.d_model
        e_ff = self.expert_d_ff
        n_moe = sum(f == "moe" for _, f in self.block_pattern) * self.num_periods
        dense = self.param_count() - n_moe * self.num_experts * 3 * d * e_ff
        return dense + n_moe * self.top_k * 3 * d * e_ff


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}

# architecture ids assigned to this paper (module names use underscores)
ARCH_IDS = [
    "llama3.2-3b",
    "qwen3-moe-30b-a3b",
    "granite-8b",
    "qwen3-14b",
    "musicgen-large",
    "llama4-scout-17b-a16e",
    "rwkv6-7b",
    "chameleon-34b",
    "jamba-v0.1-52b",
    "minitron-8b",
]
# paper's own models, usable with the same machinery
EXTRA_IDS = ["qwen3-8b", "qwen3-32b", "qwen2.5-7b", "qwen3-next-80b-a3b",
             "tiny"]


def _modname(arch: str) -> str:
    return arch.replace("-", "_").replace(".", "_")


def get_config(arch: str) -> ModelConfig:
    if arch.endswith("-smoke"):
        return get_config(arch[: -len("-smoke")]).reduced()
    if arch not in ARCH_IDS + EXTRA_IDS:
        raise KeyError(f"unknown arch {arch!r}; known: {ARCH_IDS + EXTRA_IDS}")
    mod = importlib.import_module(f"repro.configs.{_modname(arch)}")
    return mod.CONFIG


def get_shape(name: str) -> InputShape:
    return INPUT_SHAPES[name]


def list_archs():
    return list(ARCH_IDS)
