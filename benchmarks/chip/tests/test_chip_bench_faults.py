"""The chip benchmark's check catches a broken timed path.

Each test drives a whole run of a cell (set-up, window, check) on the CPU
at a tiny Qwen3 shape, without the harness's look for a chip: once sound,
where it must come out correct and the float8 control, put in the
program's place, must not; and once with each fault of ``faults.py``
planted underneath, where it must not come out correct.
"""
import time

import jax
import pytest

from benchmarks.chip import check, faults, harness, spec

TINY = {"num_hidden_layers": 1, "hidden_size": 32,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 16,
        "intermediate_size": 64, "vocab_size": 300}
# the tiny shape's own gradient limit: its sound run reads 0.002 here,
# above short-turn's 0.0015 at Qwen3-8B widths on the chip
TRAFFIC = {"batch_size": 4, "group_size": 2, "steps_per_dispatch": 4,
           "limits": {"train_grad_gap": 0.006}}
PLANE = {"MAX_SLOTS": 4, "WARMUP_STEPS": 3}
SEED = 2 ** 31 + 5
# the number each fault must push over its limit
CAUGHT_BY = {"unchanged": "train_update_gap", "half": "train_update_gap",
             "token": "serve_v0_lp_gap", "stale": "barrier_weight_gap"}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The short-turn cell at a tiny Qwen3 shape, on a smaller plane. The
    runs share a persistent compilation cache, as the benchmark's runs
    on the chip do, so only the first compiles the engine's programs."""
    if jax.devices()[0].platform != "cpu":
        pytest.skip("the fault runs are sized for the CPU")
    from jax._src import compilation_cache
    cell = spec.cell(spec.load_benchmark(), "qwen3-8b.short-turn")
    cell.config = dict(cell.config, **TINY)
    cell.traffic = dict(cell.traffic, **TRAFFIC)
    cache = {"jax_compilation_cache_dir":
             str(tmp_path_factory.mktemp("jax_cache")),
             "jax_persistent_cache_min_compile_time_secs": 0.0,
             "jax_persistent_cache_min_entry_size_bytes": 0}
    before = {k: getattr(jax.config, k) for k in cache}
    for k, v in cache.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            for k, v in PLANE.items():
                mp.setattr(harness, k, v)
            yield cell
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def run_once(cell):
    return harness.run(cell, SEED, 0.2, False, time.monotonic(),
                       harness.CompileLog())


@pytest.fixture(scope="module")
def sound(tiny):
    return run_once(tiny)


def test_sound_run_is_correct(sound):
    assert check.correct(sound.compared), sound.compared
    assert sound.compared["barrier_weight_gap"]["value"] == 0.0


def test_control_is_not_correct(tiny, sound):
    got = check.control(tiny.config, SEED, sound.batches, sound.program)
    assert not check.correct(check.checks(tiny.config, got)), got


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_planted_fault_is_caught(tiny, fault):
    remove = faults.plant(fault, TINY["vocab_size"])
    try:
        r = run_once(tiny)
    finally:
        remove()
    assert not check.correct(r.compared)
    c = r.compared[CAUGHT_BY[fault]]
    assert c["value"] > c["limit"], r.compared
