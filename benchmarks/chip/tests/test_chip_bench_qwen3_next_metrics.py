"""The Qwen3-Next cell's per-layer readers and ``work_qwen3_next``, fed a
made-up run: a decode-block and a prefill case reckoned by hand, the twins
of the runner, engine and rollout metrics, and a program without the
expert counters, which reads nothing."""
import dataclasses
from types import SimpleNamespace

import jax
import pytest

from benchmarks.chip import harness, spec
from benchmarks.chip import work_qwen3_next as W

BENCH = spec.load_benchmark()
CELL = "qwen3-next-80b-a3b.short-turn"
NEW = ("decode_block_roofline.qwen3-next", "train_step_roofline.qwen3-next",
       "step_mfu.qwen3-next", "moe.decode_experts_touched.qwen3-next",
       "moe.load_imbalance.qwen3-next")
# metrics of the runner, engine and rollout layers, which read no model
# shape: the cell reports each under a ``.qwen3-next`` twin
TWINS = ("engine.decode_occupancy", "runner.fetch_share", "runner.barrier_s",
         "runner.lock_wait_s", "runner.sync_s", "rollout.wasted_token_share",
         "rollout.host_share", "rollout.traj_latency_p90_s")
PEAKS = {"flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def _step(**fields):
    base = dict(fetch_s=0.5, barrier_s=0.2, lock_wait_s=0.1, sync_s=0.08,
                rollout_host_s=0.3, aborted_tokens=20, evicted_tokens=0,
                moe_decode_experts_touched=150,
                moe_decode_tokens_here=250, moe_train_tokens_here=6000,
                moe_train_load_max=150.0, moe_train_load_mean=100.0)
    base.update(fields)
    return SimpleNamespace(**base)


def _run(steps):
    cell = spec.cell(BENCH, CELL)
    return SimpleNamespace(
        cell=cell, window_s=10.0, steps=steps, chips=1, peaks=PEAKS,
        latencies=[1.2, 1.4, 1.5, 1.9],
        lengths=[[320] * 32, [300] * 32][:len(steps)],
        counters={"prefill_tokens": 5000, "decode_tokens": 400,
                  "decode_dispatches": 10, "recomputes": 8},
        plane={"slots": 8, "steps_per_dispatch": 8},
        trace={"window_s": 10.0, "busy_s": 4.0, "programs": {
            "jit__decode_block": {"seconds": 0.1, "calls": 10},
            "jit__prefill_into_slot": {"seconds": 0.5, "calls": 40},
            "jit_train_step": {"seconds": 1.0, "calls": 2}}})


def test_shapes_count_the_systems_parameters():
    """The parameters ``work_qwen3_next`` counts are those the system
    builds from the configuration file."""
    from repro.models import Model
    cfg = spec.cell(BENCH, CELL).config
    model = Model(harness.model_config(cfg))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    s = W.Shapes.from_config(cfg)
    assert s.total_params == n == 323_677_248
    assert s.dense_matmul_params == 184_000_512



def test_qwen3_next_file_builds_the_systems_chip_share():
    """``configs/qwen3-next-80b-a3b-4l.json`` builds the model the system
    runs at one chip's share of Qwen3-Next (``CHIP_SHARE``)."""
    from repro.configs.qwen3_next_80b_a3b import CHIP_SHARE
    ours = harness.model_config(spec.cell(BENCH, CELL).config)
    for f in dataclasses.fields(ours):
        if f.name not in ("name", "source"):
            assert getattr(ours, f.name) == getattr(CHIP_SHARE, f.name), \
                f.name

def test_decode_block_case_reckoned_by_hand():
    """10 dispatches of 8 scanned steps, 400 tokens, 300 held experts
    touched, 500 assignments here. Bytes: 80 x 2 x 184,000,512 dense
    weights + 300 x 2 x 3,145,728 expert weights + 400 x (3 layers x 2 x
    2,097,152 state + 2,048 K/V) = 36,361,502,720; FLOPs 2 x 184,000,512
    x 400 + 2 x 3,145,728 x 500 = 150,346,137,600: memory bound."""
    s = W.Shapes.from_config(spec.cell(BENCH, CELL).config)
    w = W.decode_work(s, 10, 8, 400, touched=300, assignments=500)
    assert w == {"flops": 150_346_137_600.0, "bytes": 36_361_502_720}
    run = _run([_step(), _step()])
    got = spec.reader("decode_block_roofline.qwen3-next")(run)
    assert got == pytest.approx(100.0 * 36_361_502_720 / 819e9 / 0.1)


def test_prefill_case_reckoned_by_hand():
    """40 prefill calls, 5,000 real prompt tokens. FLOPs: 2 x 145,104,896
    layer matrix params x 5,000 + 2 x 38,895,616 LM head x 40 =
    1,454,160,609,280. Bytes: 40 x (2 x 184,000,512 weights + 3 layers x
    2,097,152 state) + 5,000 x 2,048 K/V = 14,981,939,200: memory bound,
    18.3 ms at 819 GB/s."""
    s = W.Shapes.from_config(spec.cell(BENCH, CELL).config)
    assert s.layer_dense_params == 145_104_896
    w = W.prefill_work(s, 40, 5000)
    assert w == {"flops": 1_454_160_609_280.0, "bytes": 14_981_939_200}
    got = spec.reader("prefill_roofline.qwen3-next")(_run([_step()]))
    assert got == pytest.approx(100.0 * 14_981_939_200 / 819e9 / 0.5)


@pytest.mark.parametrize("name", TWINS)
def test_twin_reads_what_its_metric_reads(name):
    run = _run([_step(), _step(lock_wait_s=0.3, fetch_s=0.2)])
    value = spec.reader(f"{name}.qwen3-next")(run)
    assert value is not None
    assert value == spec.reader(name)(run)


def test_readers_read_the_counters():
    run = _run([_step(), _step(moe_train_load_max=300.0)])
    read = {n: spec.reader(n)(run) for n in NEW}
    assert read["moe.decode_experts_touched.qwen3-next"] == \
        pytest.approx(100.0 * 300 / (8 * 4 * 80))
    assert read["moe.load_imbalance.qwen3-next"] == pytest.approx(2.25)
    s = W.Shapes.from_config(run.cell.config)
    rows = [320] * 32 + [300] * 32
    train = W.train_work(s, rows, 2, 12000, 64 * harness.SEQ_LEN)
    assert train["flops"] == pytest.approx(
        6.0 * 184_000_512 * sum(rows)
        + 6.0 * 3_145_728 * 12000 * sum(rows) / (64 * 640))
    assert read["train_step_roofline.qwen3-next"] == pytest.approx(
        100.0 * W.least_seconds(train, PEAKS) / 1.0)
    flops = (train["flops"] + 2.0 * s.layer_dense_params * 5000
             + 2.0 * 184_000_512 * 400 + 2.0 * 3_145_728 * 500)
    assert read["step_mfu.qwen3-next"] == pytest.approx(
        100.0 * flops / (10.0 * 197e12))
    assert spec.reader("device.idle_share.qwen3-next")(run) == \
        pytest.approx(60.0)
    for name in NEW:
        assert 0 < read[name]


@pytest.mark.parametrize("name", NEW)
def test_program_without_the_counters_reads_none(name):
    """A program that predates the expert counters (its ``StepMetrics``
    lack the fields) gives nothing to read."""
    parent = SimpleNamespace(fetch_s=0.5, barrier_s=0.2)
    assert spec.reader(name)(_run([parent, parent])) is None


def test_entries_name_the_cell():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW + ("device.idle_share.qwen3-next",
                       "prefill_roofline.qwen3-next"):
        assert entries[name]["workloads"] == [CELL]
    for name in TWINS:
        twin = entries[f"{name}.qwen3-next"]
        assert twin["workloads"] == [CELL]
        assert {k: v for k, v in twin.items() if k not in ("name",
                                                           "workloads")} \
            == {k: v for k, v in entries[name].items()
                if k not in ("name", "workloads")}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for name in ("step_s", "traj_tokens_per_s"):
        assert CELL in e2e[name]["workloads"]
