"""Operations, bytes and peaks of the chip benchmark (benchmarks/chip/work.py)."""
import json
from pathlib import Path

import pytest

from benchmarks.chip import work

CONFIG = Path(__file__).resolve().parents[1] / "configs" / "qwen3-8b-1l.json"


@pytest.fixture(scope="module")
def qwen():
    return work.Shapes.from_config(json.loads(CONFIG.read_text()))


def test_qwen3_8b_one_layer_param_count(qwen):
    # one layer of 192,946,432 (matrices 192,937,984, the two RMSNorm
    # scales 8,192, the q/k norm scales 256), the final norm 4,096, and
    # the LM head and the embedding 77,791,232 each
    assert qwen.total_params == 348_532_992
    assert qwen.matmul_params == 270_729_216


def test_decode_step_reads_bf16_matrices_once(qwen):
    w = work.decode_work(qwen, dispatches=1, steps_per_dispatch=1, tokens=0)
    assert w["bytes"] == 541_458_432
    assert w["flops"] == 0


def test_hand_worked_small_case():
    s = work.Shapes(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4,
                    d_ff=16, vocab=10)
    # per layer: q,o 2*8*2*4=128, k,v 2*8*1*4=64, mlp 3*8*16=384
    assert s.layer_matrix_params == 576
    assert s.matmul_params == 2 * 576 + 80
    assert s.kv_bytes_per_token == 2 * 2 * 1 * 4 * 2
    # decode: 3 dispatches of 4 steps, 10 tokens emitted
    d = work.decode_work(s, 3, 4, 10)
    assert d["flops"] == 2 * 1232 * 10
    assert d["bytes"] == 12 * 2 * 1232 + 10 * 32
    # prefill: 2 calls, 7 real tokens
    p = work.prefill_work(s, 2, 7)
    assert p["flops"] == 2 * (2 * 576 * 7 + 80 * 2)
    assert p["bytes"] == 2 * 2 * 1232 + 7 * 32
    # train: rows of 3 and 1 tokens; causal pairs 6 and 1 per layer
    t = work.train_work(s, [3, 1])
    attn = 2 * 4 * 2 * 4 * (6 + 1)
    assert t["flops"] == 6 * 1232 * 4 + 3 * attn
    assert t["bytes"] == 24 * s.total_params
    peak = {"flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(t, peak) == max(t["flops"] / 100.0,
                                              t["bytes"] / 10.0)


def test_peaks_by_device_kind():
    assert work.peaks("TPU v5 lite")["flops"] == 197e12
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="TPU v9"):
        work.peaks("TPU v9")
