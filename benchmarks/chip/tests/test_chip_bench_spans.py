"""The per-layer readers of the program's spans and counters: the weight-sync
barrier's lock wait and sync, the rollout's wasted tokens and host share,
and their long-horizon twins, fed a made-up run."""
from types import SimpleNamespace

import pytest

from benchmarks.chip import spec

BENCH = spec.load_benchmark()
READERS = ("runner.lock_wait_s", "runner.sync_s",
           "rollout.wasted_token_share", "rollout.host_share")
TWINS = tuple(f"{name}.long-horizon" for name in READERS)


def _step(**fields):
    base = dict(fetch_s=0.1, barrier_s=1.1, push_wait_s=0.05,
                lock_wait_s=0.85, sync_s=0.2, rollout_host_s=0.6,
                aborted_tokens=120, evicted_tokens=30)
    base.update(fields)
    return SimpleNamespace(**base)


def _run(steps, window_s=2.5, decode_tokens=3000):
    return SimpleNamespace(window_s=window_s, steps=steps,
                           counters={"decode_tokens": decode_tokens})


def _parent_step():
    """A step of a program that has no such spans or counters."""
    return SimpleNamespace(fetch_s=0.1, barrier_s=1.1)


def test_readers_read_the_steps():
    run = _run([_step(), _step(lock_wait_s=0.65, sync_s=0.3,
                               rollout_host_s=0.9, aborted_tokens=0,
                               evicted_tokens=0)])
    got = {name: spec.reader(name)(run) for name in READERS}
    assert got["runner.lock_wait_s"] == pytest.approx(0.75)
    assert got["runner.sync_s"] == pytest.approx(0.25)
    assert got["rollout.wasted_token_share"] == pytest.approx(
        100.0 * 150 / 3000)
    assert got["rollout.host_share"] == pytest.approx(100.0 * 1.5 / 2.5)


@pytest.mark.parametrize("twin", TWINS)
def test_twin_reads_what_its_metric_reads(twin):
    run = _run([_step(), _step(lock_wait_s=2.0)])
    value = spec.reader(twin)(run)
    assert value is not None
    assert value == spec.reader(twin[:-len(".long-horizon")])(run)


@pytest.mark.parametrize("name", READERS + TWINS)
def test_window_without_steps_reads_none(name):
    assert spec.reader(name)(_run([])) is None


@pytest.mark.parametrize("name", READERS + TWINS)
def test_program_without_the_fields_reads_none(name):
    """A program that predates these spans and counters gives nothing to
    read, and the reader says so rather than raising."""
    assert spec.reader(name)(_run([_parent_step(), _parent_step()])) is None


def test_wasted_share_needs_decoded_tokens():
    assert spec.reader("rollout.wasted_token_share")(
        _run([_step()], decode_tokens=0)) is None


def test_entries_follow_the_cells():
    """Each reader is reported in short-turn, where it moves the step time
    or the token rate, and its twin in long-horizon, where it moves the
    trajectory latency."""
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name, twin in zip(READERS, TWINS):
        a, b = entries[name], entries[twin]
        assert a["workloads"] == ["qwen3-8b.short-turn"]
        assert b["workloads"] == ["qwen3-8b.long-horizon"]
        assert b["moves"] == "traj_latency_p90_s"
        assert a["moves"] in ("step_s", "traj_tokens_per_s")
        for k in ("unit", "better", "source", "layer"):
            assert a[k] == b[k], (name, k)
