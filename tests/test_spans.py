"""Spans and counters inside the live RL loop (``repro.obs.spans``): the
weight-sync barrier split into push wait, lock wait and sync; the spans a
profiler trace of the loop holds; the wasted-token and rollout-host
counters; and the jitted program names the chip benchmark's roofline
readers key on, which are what the device trace shows."""
import glob
import re
import threading
import time
from types import SimpleNamespace

import jax
import pytest

from repro.configs import get_config
from repro.core import (EngineHandle, LiveRLRunner, LLMProxy, RunnerConfig,
                        ServerlessPlatform)
from repro.core.buffer import SampleBuffer
from repro.core.envmanager import EMState
from repro.data.pipeline import Trajectory
from repro.launch.mesh import make_group_mesh
from repro.models import Model
from repro.obs.spans import SPANS, phase
from repro.rewards.rule_based import format_bonus_reward
from repro.rl.engine import InferenceEngine
from repro.rl.trainer import (default_optimizer, init_train_state,
                              make_grpo_train_step)


def _make_runner(mode, store_engine=False, **cfg_kw):
    """The tiny loop over one engine on the trainer's device (it takes the
    trainer's arrays at each barrier); ``store_engine`` adds a second, a
    one-device TP group, which pulls every version from the store."""
    model = Model(get_config("tiny"), remat=False)
    opt = default_optimizer(1e-3)
    state = init_train_state(model, jax.random.PRNGKey(0), opt)
    handles = [EngineHandle(InferenceEngine(model, state.params,
                                            max_slots=8, max_len=320,
                                            seed=3), "H20")]
    if store_engine:
        handles.append(EngineHandle(InferenceEngine(
            model, state.params, max_slots=8, max_len=320, seed=4,
            mesh=make_group_mesh(jax.devices()[:1])), "H20"))
    kw = dict(batch_size=4, group_size=2, alpha=1, tasks=("math",),
              max_new_tokens=12, temperature=0.0)
    kw.update(cfg_kw)
    return LiveRLRunner(
        RunnerConfig(mode=mode, **kw), LLMProxy(handles),
        state, jax.jit(make_grpo_train_step(model, opt)),
        ServerlessPlatform(), format_bonus_reward, seq_len=320)


class _Capture:
    """A jitted program, called through: keeps the abstract arguments of
    its first call, to lower it again afterwards."""

    def __init__(self, fn):
        self.fn, self.args = fn, None

    def __call__(self, *args):
        if self.args is None:
            self.args = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
                if isinstance(x, jax.Array) else x, args)
        return self.fn(*args)

    def module_name(self) -> str:
        text = self.fn.lower(*self.args).as_text()
        return re.search(r"module @(\S+)", text).group(1)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two steps of the threaded loop under a profiler session, on a
    plane with both weight paths: the device path and the store's."""
    runner = _make_runner("rollart", store_engine=True)
    eng = runner.proxy.handles[0].engine
    jits = {"decode": _Capture(eng._decode_block_jit),
            "prefill": _Capture(eng._prefill_jit),
            "train": _Capture(runner.train_step_fn)}
    eng._decode_block_jit, eng._prefill_jit = jits["decode"], jits["prefill"]
    runner.train_step_fn = jits["train"]
    out = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        with runner:
            history = runner.run_steps(2)
    finally:
        jax.profiler.stop_trace()
    timings = runner.service.timings()
    [path] = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
    from jax.profiler import ProfileData
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    return SimpleNamespace(history=history, names=names, jits=jits,
                           timings=timings)


def test_trace_holds_every_span_and_no_other(traced):
    assert len(SPANS) == len(set(SPANS))
    assert {n for n in traced.names if n.startswith("repro.")} == set(SPANS)


def test_barrier_parts_make_up_barrier_s(traced):
    for s in traced.history:
        parts = (s.push_wait_s, s.lock_wait_s, s.sync_s)
        assert min(parts) >= 0
        assert sum(parts) == pytest.approx(s.barrier_s, abs=1e-3)
        assert s.fetch_s + s.barrier_s + s.train_s <= s.wall_s
        assert s.rollout_host_s >= 0


def test_service_thread_parks_for_the_barriers(traced):
    """The rollout thread parks between ticks for a waiting barrier
    (``repro.service.handoff``): at most once a barrier, one a step."""
    t = traced.timings
    assert 1 <= t["handoffs"] <= len(traced.history)
    assert t["handoff_s"] > 0


def test_roofline_readers_key_on_the_jitted_program_names(traced):
    """The device trace names a program after its jitted function; the
    roofline readers find the engine's decode block and prefill and the
    GRPO step by these names, so a rename must fail here, not silently
    empty a roofline metric."""
    from benchmarks.chip import spec
    pinned = {"decode_block_roofline": ("decode", "jit__decode_block"),
              "prefill_roofline": ("prefill", "jit__prefill_into_slot"),
              "train_step_roofline": ("train", "jit_train_step")}
    for reader, (key, program) in pinned.items():
        assert traced.jits[key].module_name() == program
        mod = spec.load_module(spec.metric_path(reader),
                               f"benchmarks.chip.metrics.{reader}")
        assert mod.PROGRAM == program


def test_lock_held_before_barrier_shows_as_lock_wait():
    """A thread holding the service lock when the barrier starts holds
    the trainer there: its step's lock wait reads the hold, the other
    step's reads next to nothing, and the rollout's host seconds are what
    the ticks held the lock for less what they waited on the chip."""
    hold = 0.05
    runner = _make_runner("sync")
    await_batch, held_at = runner._await_batch, []

    def await_batch_then_hold():
        batch = await_batch()
        if held_at:
            return batch
        held = threading.Event()

        def holder():
            with runner.service._lock:
                held_at.append(time.monotonic())
                held.set()
                # the hold runs from the barrier's start: 5 ms for the
                # trainer to get from here to the lock
                time.sleep(hold + 0.005)

        threading.Thread(target=holder, daemon=True).start()
        held.wait()
        return batch

    runner._await_batch = await_batch_then_hold
    eng = runner.proxy.handles[0].engine
    with runner:
        tick0, wait0 = runner.service.tick_s, eng.device_wait_s
        first, second = runner.run_steps(2)
    assert first.lock_wait_s >= hold
    assert second.lock_wait_s < 0.01
    for s in (first, second):
        assert s.push_wait_s + s.lock_wait_s + s.sync_s == \
            pytest.approx(s.barrier_s, abs=1e-3)
        assert s.rollout_host_s > 0
    # every pump of a sync mode runs in a tick or a drain
    assert first.rollout_host_s + second.rollout_host_s == pytest.approx(
        (runner.service.tick_s - tick0) - (eng.device_wait_s - wait0))
    timings = runner.service.timings()
    assert timings["tick_host_s"] == pytest.approx(
        first.rollout_host_s + second.rollout_host_s)
    assert timings["barrier_wait_s"] >= hold


def _partial_tokens(eng, rid):
    return next((len(s.new_tokens) for s in eng._slots
                 if s.active and s.request.request_id == rid), 0)


def test_forced_staleness_abort_counts_generated_tokens():
    """Trajectories the staleness bound aborts count what they generated:
    their finished turns and the aborted request's partial output."""
    runner = _make_runner("rollart")
    eng = runner.proxy.handles[0].engine
    tenant = runner.service.tenant("trainer")
    for _ in range(200):
        runner.service.tick()
        if any(em.turns >= 1 and em.state == EMState.GENERATING
               and _partial_tokens(eng, em._active_req) > 0
               for em in runner.active):
            break
    victims = [em for em in runner.active if em.state == EMState.GENERATING]
    turns = sum(sum(em.loss_mask) for em in victims)
    partial = sum(_partial_tokens(eng, em._active_req) for em in victims)
    assert turns > 0 and partial > 0
    runner.version = runner.cfg.alpha + 1    # every manager started at 0
    runner._enforce_staleness()              # is now out of bound
    runner.version = 0
    while any(em in runner.active for em in victims):
        runner.service.tick()
    assert all(em.state == EMState.ABORTED for em in victims)
    assert tenant.stats["aborted_tokens"] == turns + partial
    # the next step reports them as its own (no abort happens in step 0)
    with runner:
        [step] = runner.run_steps(1)
    assert step.aborted_tokens == turns + partial


def test_buffer_counts_evicted_tokens():
    buf = SampleBuffer(alpha=1)
    for i, mask in enumerate(([0, 1, 1], [0, 0, 1, 1, 1])):
        buf.put(Trajectory(traj_id=f"t{i}", task="math",
                           tokens=[1] * len(mask), loss_mask=mask,
                           logprobs=[0.0] * len(mask), start_version=0))
    buf.set_version(2)
    assert buf.total_evicted == 2
    assert buf.total_evicted_tokens == 5
    assert buf.stats()["total_evicted_tokens"] == 5


def test_phase_times_its_span():
    with phase("repro.runner.step") as ph:
        time.sleep(0.01)
    assert ph.seconds >= 0.01 and ph.t1 > ph.t0
