"""Hardware taxonomy + analytic performance model.

GPU entries use the paper's Table 2 (H800/H20) so the benchmarks can
validate against the paper's measured ratios; TPU entries are the
deployment target per DESIGN.md §2. The performance model is a two-phase
(prefill=compute-bound, decode=bandwidth-bound) latency estimate with
efficiency factors calibrated once in ``benchmarks/calibration.py`` to
reproduce the paper's Fig. 4 ratios (H800 0.53x prefill-heavy; H20
0.49-0.79x decode-heavy).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

from repro.configs.base import ModelConfig

# Role -> hardware-class affinity (paper §5.2 / Table 2 / Fig. 4):
# compute-bound prefill belongs on compute-class chips (H800/TPUv5p),
# bandwidth-bound decode on bandwidth-class chips (H20/TPUv5e). Colocated
# engines serve both phases; the prefill phase is the one that saturates
# first on a mismatched chip, so they default to compute-class.
ROLE_CLASS_AFFINITY: Dict[str, str] = {
    "prefill": "compute",
    "decode": "bandwidth",
    "colocated": "compute",
    "train": "compute",
    "generate": "bandwidth",
    "environment": "host",
    "reward": "elastic",
}


@dataclass(frozen=True)
class HardwareSpec:
    name: str
    kind: str                 # "gpu" | "tpu" | "cpu" | "serverless"
    klass: str                # "compute" | "bandwidth" | "host" | "elastic"
    tflops_bf16: float        # peak TFLOP/s per device
    hbm_gb: float
    hbm_bw_gbs: float         # GB/s
    link_bw_gbs: float        # interconnect per device
    norm_cost: float          # normalized $ cost (paper Table 2)


# --- paper Table 2 ---------------------------------------------------------
H800 = HardwareSpec("H800", "gpu", "compute", 989.5, 80, 3350, 400, 2.85)
H20 = HardwareSpec("H20", "gpu", "bandwidth", 148.0, 96, 4000, 900, 1.00)
# --- TPU deployment target (assignment roofline constants for v5e) ---------
TPU_V5E = HardwareSpec("TPUv5e", "tpu", "bandwidth", 197.0, 16, 819, 50, 0.7)
TPU_V5P = HardwareSpec("TPUv5p", "tpu", "compute", 459.0, 95, 2765, 100, 2.2)
CPU_HOST = HardwareSpec("CPU", "cpu", "host", 0.0, 0, 0, 10, 0.05)
SERVERLESS = HardwareSpec("Serverless", "serverless", "elastic",
                          148.0, 96, 4000, 10, 0.0)

REGISTRY: Dict[str, HardwareSpec] = {
    h.name: h for h in [H800, H20, TPU_V5E, TPU_V5P, CPU_HOST, SERVERLESS]
}

# jax ``Device.device_kind`` of each TPU chip in REGISTRY
TPU_KINDS: Dict[str, str] = {
    "TPU v5 lite": TPU_V5E.name,
    "TPU v5": TPU_V5P.name,
}


def device_pool(device) -> str:
    """REGISTRY name of the hardware a live engine runs on (a jax
    ``Device``). CPU devices map to the CPU host entry. An accelerator
    whose kind has no entry raises: pricing it with another chip's peaks
    would be a silent lie."""
    if device.platform == "cpu":
        return CPU_HOST.name
    name = TPU_KINDS.get(device.device_kind) if device.platform == "tpu" \
        else None
    if name is None:
        raise ValueError(
            f"no HardwareSpec for {device.platform} device kind "
            f"{device.device_kind!r}; add it to repro.core.hardware")
    return name


# --- efficiency factors (calibrated against paper Fig. 4; see
#     benchmarks/calibration.py for the fit) --------------------------------
@dataclass
class PerfModel:
    prefill_mfu: float = 0.50         # fraction of peak FLOPs in prefill
    decode_bw_eff: float = 0.55       # fraction of peak HBM bw in decode
    decode_overhead_s: float = 0.001  # per-token scheduling overhead
    step_overhead_s: float = 0.3      # per generation request overhead

    def prefill_time(self, cfg: ModelConfig, prompt_tokens: int,
                     hw: HardwareSpec, tp_degree: int,
                     prefix_cached_frac: float = 0.0) -> float:
        """Compute-bound, per TP serving group: 2*N_active*T/(tp*peak*mfu)."""
        flops = 2.0 * cfg.active_param_count() * prompt_tokens \
            * (1.0 - prefix_cached_frac)
        return flops / max(tp_degree * hw.tflops_bf16 * 1e12
                           * self.prefill_mfu, 1.0)

    def kv_bytes_per_token(self, cfg: ModelConfig) -> float:
        if cfg.attention_free:
            return 0.0
        n_attn = sum(m == "attn" for m, _ in cfg.block_pattern) \
            * cfg.num_periods
        return 2.0 * cfg.num_kv_heads * cfg.head_dim * n_attn * 2.0

    def decode_time(self, cfg: ModelConfig, new_tokens: int,
                    hw: HardwareSpec, tp_degree: int,
                    context: int = 8192, concurrency: int = 32) -> float:
        """Bandwidth-bound, per TP serving group. Per engine step the group
        reads the weights ONCE for all ``concurrency`` streams plus each
        stream's KV cache (context * kv_bytes); at long contexts the KV
        traffic dominates — which is exactly why decode-heavy tasks prefer
        bandwidth-optimized chips (R1)."""
        weights = 2.0 * cfg.active_param_count()
        kv = context * self.kv_bytes_per_token(cfg)
        bw = tp_degree * hw.hbm_bw_gbs * 1e9 * self.decode_bw_eff
        # one engine step serves all streams: weights once + every stream's
        # KV cache; each stream advances one token per step
        t_step = (weights + max(concurrency, 1) * kv) / max(bw, 1.0)
        return new_tokens * (t_step + self.decode_overhead_s)

    def train_step_time(self, cfg: ModelConfig, batch_tokens: int,
                        hw: HardwareSpec, n_devices: int,
                        mfu: float = 0.35) -> float:
        flops = 6.0 * cfg.active_param_count() * batch_tokens
        return flops / max(n_devices * hw.tflops_bf16 * 1e12 * mfu, 1.0)

    def weight_bytes(self, cfg: ModelConfig) -> float:
        return 2.0 * cfg.param_count()

    def transfer_time(self, nbytes: float, bw_gbs: float,
                      latency_s: float = 0.005) -> float:
        return latency_s + nbytes / (bw_gbs * 1e9)

    # -- placement pricing (§5.2: the PerfModel as the placement layer) ----
    def role_latency(self, cfg: ModelConfig, role: str, hw: HardwareSpec,
                     tp_degree: int = 1, *, prompt_tokens: int = 512,
                     new_tokens: int = 256, concurrency: int = 32) -> float:
        """Modeled per-request latency of one serving group in ``role`` on
        ``hw``: the prefill phase for prefill-role, the decode loop for
        decode-role, and their sum for a colocated engine."""
        t_p = self.prefill_time(cfg, prompt_tokens, hw, tp_degree)
        t_d = self.decode_time(cfg, new_tokens, hw, tp_degree,
                               context=prompt_tokens + new_tokens,
                               concurrency=concurrency)
        return {"prefill": t_p, "decode": t_d}.get(role, t_p + t_d)

    def price_placement(self, cfg: ModelConfig, prefill_hw: HardwareSpec,
                        decode_hw: HardwareSpec, *, n_prefill: int = 1,
                        n_decode: int = 1, prompt_tokens: int = 4096,
                        new_tokens: int = 256,
                        concurrency: int = 32) -> Dict[str, float]:
        """Price a two-stage placement: request rate of the pipeline
        (bottleneck stage), its normalized dollar cost, and the
        cost-normalized throughput the paper's Table 2 ordering is stated
        in. A prefill group serves one request at a time; a decode group
        serves ``concurrency`` streams per engine step."""
        t_p = self.prefill_time(cfg, prompt_tokens, prefill_hw, 1)
        t_d = self.decode_time(cfg, new_tokens, decode_hw, 1,
                               context=prompt_tokens + new_tokens,
                               concurrency=concurrency)
        prefill_rate = n_prefill / max(t_p, 1e-12)
        decode_rate = n_decode * max(concurrency, 1) / max(t_d, 1e-12)
        rate = min(prefill_rate, decode_rate)
        cost = n_prefill * prefill_hw.norm_cost + n_decode * decode_hw.norm_cost
        return {
            "prefill_s": t_p, "decode_s": t_d,
            "prefill_rate_rps": prefill_rate, "decode_rate_rps": decode_rate,
            "rate_rps": rate, "norm_cost": cost,
            "tokens_per_s": rate * (prompt_tokens + new_tokens),
            "cost_norm_throughput": rate / max(cost, 1e-12),
        }


PERF = PerfModel()
