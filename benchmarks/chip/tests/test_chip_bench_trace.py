"""Trace reduction of the chip benchmark (benchmarks/chip/xplane.py), on a
trace recorded on a TPU v5e: the first 0.6 s of a traced run's window of
``qwen3-8b.short-turn``, cut down with ``xplane.trim``. The numbers beside
it were read from it when it was cut."""
import gzip
import json
from pathlib import Path

import pytest

from benchmarks.chip import xplane

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    raw = gzip.decompress((DATA / "short_turn_window.xplane.pb.gz")
                          .read_bytes())
    return xplane.reduce(ProfileData.from_serialized_xspace(raw))


@pytest.fixture(scope="module")
def expected():
    return json.loads((DATA / "short_turn_window.json").read_text())


def test_window_busy_and_idle(reduced, expected):
    assert reduced["chips"] == expected["chips"] == 1
    assert reduced["window_s"] == pytest.approx(expected["window_s"])
    assert reduced["busy_s"] == pytest.approx(expected["busy_s"])
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_program_device_time(reduced, expected):
    assert set(reduced["programs"]) == set(expected["programs"])
    for name, p in expected["programs"].items():
        assert reduced["programs"][name]["calls"] == p["calls"], name
        assert reduced["programs"][name]["seconds"] == \
            pytest.approx(p["seconds"]), name
    assert "jit__decode_block" in reduced["programs"]


def test_top_ops_and_named_gaps(reduced, expected):
    assert [n for n, _ in reduced["device_ops"]] == \
        [n for n, _ in expected["device_ops"]]
    assert [n for n, _ in reduced["idle_gaps"]] == \
        [n for n, _ in expected["idle_gaps"]]
    gaps = [s for _, s in reduced["idle_gaps"]]
    assert gaps == pytest.approx([s for _, s in expected["idle_gaps"]])
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= reduced["window_s"] - reduced["busy_s"] + 1e-9


def test_union_merges_overlaps():
    assert xplane.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3),
                                                              (5, 10)]


def test_program_and_op_names():
    assert xplane.program_name("jit__decode_block(1146860)") == \
        "jit__decode_block"
    assert xplane.op_name("%fusion.221 = bf16[8] fusion(%p)") == "fusion.221"
