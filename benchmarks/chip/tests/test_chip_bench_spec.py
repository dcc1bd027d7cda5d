"""The chip benchmark as data: BENCHMARK.json, the files it names, and the
plane the harness builds (benchmarks/chip)."""
import dataclasses
import importlib.util
import os
import subprocess
import sys

import jax
import pytest

from benchmarks.chip import harness, spec

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_its_files(name):
    cell = spec.cell(BENCH, name)
    assert cell.config["name"] == cell.config_name
    assert spec.traffic_path(cell.traffic_name).is_file()
    assert cell.chips in (1, 4)
    for m in cell.per_layer:
        assert callable(spec.reader(m["name"]))


def test_names_and_units_use_allowed_characters():
    named = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
             + BENCH["per_layer"])
    for entry in named:
        assert spec.NAME.match(entry["name"]), entry["name"]
    for w in BENCH["workloads"]:
        assert spec.NAME.match(w["config"]) and spec.NAME.match(w["traffic"])
    for c in BENCH["configs"]:
        assert all(spec.NAME.match(k) for k in c["reduced"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert spec.UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[kind]]
        assert len(names) == len(set(names)), kind


def test_every_moved_metric_is_reported_where_its_reader_reports():
    for name in CELLS:
        cell = spec.cell(BENCH, name)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e, (name, m["name"])


@pytest.mark.parametrize("mix", sorted({w["traffic"]
                                         for w in BENCH["workloads"]}))
def test_plane_at_seed_zero_is_the_launchers(mix):
    from repro.configs.base import ModelConfig
    from repro.launch import train as T
    from repro.models import Model
    from repro.rl.trainer import (default_optimizer, init_train_state,
                                  make_grpo_train_step)
    traffic = spec.cell(BENCH, next(w["name"] for w in BENCH["workloads"]
                                     if w["traffic"] == mix)).traffic
    m = Model(ModelConfig(name="t", family="dense", source="test",
                          num_layers=1, d_model=32, num_heads=2,
                          num_kv_heads=1, head_dim=16, d_ff=64,
                          vocab_size=300, qk_norm=True), remat=False)
    opt = default_optimizer(harness.LR)
    state = init_train_state(m, jax.random.PRNGKey(0), opt)
    step = make_grpo_train_step(m, opt)
    args = T.build_parser().parse_args(
        ["--tasks", ",".join(traffic["tasks"]),
         "--task-weights", ",".join(map(str, traffic["task_weights"])),
         "--batch", str(traffic["batch_size"]),
         "--group", str(traffic["group_size"]),
         "--alpha", str(traffic["alpha"]), "--mode", traffic["mode"],
         "--steps-per-dispatch", str(traffic["steps_per_dispatch"]),
         "--reward", traffic["reward"]])
    assert args.lr == harness.LR
    theirs = T.build_runner(args, m, state, step)
    ours = harness.build_runner(m, state, step, traffic, 0, lambda p: 0.0)
    try:
        assert ours.cfg == theirs.cfg
        assert ours.seq_len == theirs.seq_len
        e_o, e_t = ours.proxy.handles[0].engine, theirs.proxy.handles[0].engine
        for attr in ("max_slots", "max_len", "steps_per_dispatch", "role",
                     "tp_group", "paged", "donate"):
            assert getattr(e_o, attr) == getattr(e_t, attr), attr
        assert len(ours.proxy.handles) == len(theirs.proxy.handles) == 1
        assert ours.proxy.handles[0].pool == theirs.proxy.handles[0].pool
    finally:
        ours.close()
        theirs.close()


def test_reference_trains_as_the_launcher_does():
    """The reference's optimizer and loss constants are the launcher's:
    ``--lr``, AdamW's defaults, the GRPO step's ratio clip."""
    import inspect
    from repro.launch import train as T
    from repro.rl.trainer import default_optimizer, make_grpo_train_step
    from benchmarks.chip import reference as R
    lr = T.build_parser().parse_args([]).lr
    opt = default_optimizer(lr)
    assert R.ADAMW == {"lr": lr, "b1": opt.b1, "b2": opt.b2,
                       "eps": opt.eps, "weight_decay": opt.weight_decay,
                       "clip_norm": opt.clip_norm}
    assert R.CLIP_EPS == inspect.signature(
        make_grpo_train_step).parameters["clip_eps"].default


def test_qwen3_8b_file_builds_the_systems_one_layer_config():
    """``configs/qwen3-8b-1l.json`` builds the model the system runs at
    Qwen3-8B widths cut to one layer and an eighth of the vocabulary."""
    from repro.configs.qwen3_8b import CHIP_SMOKE
    cfg = spec.cell(BENCH, "qwen3-8b.short-turn").config
    ours = harness.model_config(cfg)
    for f in dataclasses.fields(CHIP_SMOKE):
        if f.name not in ("name", "source"):
            assert getattr(ours, f.name) == getattr(CHIP_SMOKE, f.name), \
                f.name


def test_command_refuses_a_cpu_and_names_it():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.HERE / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, cwd=spec.ROOT, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("name", CELLS)
def test_mix_limits_replace_the_configurations(name):
    """A mix names limits only for numbers the configuration compares, and
    the check holds the run to the mix's where it names one."""
    from benchmarks.chip import check
    cell = spec.cell(BENCH, name)
    own = cell.traffic.get("limits", {})
    assert set(own) <= set(cell.config["limits"])
    vals = dict.fromkeys(cell.config["limits"], 0.0)
    got = check.checks(cell.config, vals, cell.traffic.get("limits"))
    assert set(got) == set(cell.config["limits"])
    for k, c in got.items():
        assert c["limit"] == own.get(k, cell.config["limits"][k])


def _made_up_run():
    """A run's readings, made up: two steps, a traced window with both
    engine programs, and the engine's window counters."""
    from types import SimpleNamespace
    from benchmarks.chip import work
    cfg = spec.cell(BENCH, CELLS[0]).config
    step = SimpleNamespace(fetch_s=0.4, barrier_s=1.1)
    return SimpleNamespace(
        window_s=12.5, steps=[step, step], lengths=[[640] * 8, [600] * 8],
        counters={"prefill_tokens": 9000, "decode_tokens": 3000,
                  "decode_dispatches": 50, "recomputes": 8},
        plane={"slots": 8, "steps_per_dispatch": 8},
        shapes=work.Shapes.from_config(cfg), peaks=work.peaks("TPU v5 lite"),
        chips=1, compile={"setup_seconds": 10.0},
        trace={"window_s": 12.5, "busy_s": 5.0, "programs": {
            "jit__decode_block": {"seconds": 1.0, "calls": 50},
            "jit__prefill_into_slot": {"seconds": 0.5, "calls": 30},
            "jit_train_step": {"seconds": 0.3, "calls": 2}}},
        lifecycles=[])


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]
                                  if m["name"].endswith(".latency")])
def test_latency_twin_reads_what_its_metric_reads(name):
    """A ``<metric>.latency`` reader, reported where the metric moves the
    trajectory latency, reads the same number as ``<metric>``."""
    run = _made_up_run()
    value = spec.reader(name)(run)
    assert value is not None
    assert value == spec.reader(name[:-len(".latency")])(run)


@pytest.mark.parametrize("reader, name", [
    ("runner.step_s", "step_s"),
    ("runner.traj_tokens_per_s", "traj_tokens_per_s"),
    ("rollout.traj_latency_p90_s", "traj_latency_p90_s")])
def test_per_layer_reading_is_the_end_to_end_metric(reader, name):
    """Where an end-to-end metric is read per layer, in the cells that do
    not hold it to a bound, it is read as the end-to-end metric is."""
    spec_ = importlib.util.spec_from_file_location(
        "bench_run", spec.HERE / "run.py")
    run_py = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(run_py)
    run = _made_up_run()
    run.setup_s = 30.0
    run.latencies = [1.5, 0.75, 2.0, 1.25, 3.0, 1.0, 0.5, 2.5, 1.75, 2.25, 4.0]
    value = spec.reader(reader)(run)
    assert value is not None and value == run_py.end_to_end(run)[name]
