"""Rollout-as-a-Service tier (ROADMAP item 1): multi-tenant admission,
stride-weighted QoS, streaming token delivery, and lifecycle safety.

Streaming-ordering coverage (the satellite contract): per-job token
streams must be monotonic and gap-free — ``TokenStream.tokens_for``
asserts chunk tiling internally — across

- plain single-engine generation,
- a PD prefill->decode engine handoff,
- a suspend -> update_all -> resume weight-sync barrier mid-stream,
- an abort mid-stream, and
- an injected engine kill + supervised FT recovery (a second, streamed
  tenant riding on the trainer's service).

Plus: stride shares track configured weights under overload, full queues
reject at submit (backpressure), ``LiveRLRunner.close`` is idempotent
and exception-safe (double-close, close-after-crash), and the service
thread hands its lock to a waiting weight-sync barrier after the tick in
flight (counted in ticks, not wall time).
"""
import inspect
import os
import sys
import threading
import time

import jax
import pytest

from repro.configs import get_config
from repro.core import (EngineHandle, LiveRLRunner, LLMProxy, RunnerConfig,
                        ServerlessPlatform, build_pd_proxy)
from repro.core.envmanager import EMState, RolloutPolicy
from repro.envs import make_env
from repro.ft import FTConfig, FTSupervisor, FailureInjector
from repro.models import Model
from repro.rewards.rule_based import REWARD_FNS
from repro.rl.engine import GenRequest, InferenceEngine
from repro.rl.trainer import (default_optimizer, init_train_state,
                              make_grpo_train_step)
from repro.serve import JobState, RolloutJob, RolloutService, TokenStream


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = get_config("tiny")
    model = Model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _make_service(model, params, *, max_slots=4, max_len=128, seed=3):
    eng = InferenceEngine(model, params, max_slots=max_slots,
                          max_len=max_len, seed=seed)
    return RolloutService(LLMProxy([EngineHandle(eng, "H20")]))


def _tick_until(svc, pred, limit=3000):
    for _ in range(limit):
        if pred():
            return
        svc.tick()
    raise AssertionError("condition not reached within tick limit")


def _assert_stream_matches(ticket):
    """The stream must reproduce the job's final result exactly, with
    gap-free chunk tiling (tokens_for asserts contiguity)."""
    [res] = ticket.results
    rid = f"{ticket.job_id}.r0"
    assert ticket.stream.tokens_for(rid) == res.tokens
    assert ticket.stream.result_tokens(timeout=1) == res.tokens
    lp = [p for c in ticket.stream.chunks() for p in c.logprobs]
    assert lp == res.logprobs


# ---------------------------------------------------------------------------
# TokenStream: idempotent cumulative delivery
# ---------------------------------------------------------------------------
def test_token_stream_idempotent_and_gap_free():
    st = TokenStream("j0")
    assert st.push("r", [1, 2, 3], [-0.1, -0.2, -0.3]) == 3
    assert st.push("r", [1, 2, 3], [-0.1, -0.2, -0.3]) == 0   # replay
    assert st.push("r", [1, 2], [-0.1, -0.2]) == 0            # shorter
    assert st.push("r", [1, 2, 3, 4, 5], [-0.1, -0.2, -0.3, -0.4, -0.5]) == 2
    assert st.tokens_for("r") == [1, 2, 3, 4, 5]
    assert st.token_count() == 5
    starts = [c.start for c in st.chunks()]
    ends = [c.end for c in st.chunks()]
    assert starts == [0, 3] and ends == [3, 5]
    st.close("stop")
    st.close("aborted")                   # idempotent: first close wins
    assert st.closed and st.finish_reason == "stop"
    assert st.push("r", list(range(9)), [0.0] * 9) == 0   # closed: no-op
    assert st.result_tokens(timeout=1) == [1, 2, 3, 4, 5]


def test_token_stream_multiplexes_request_ids():
    st = TokenStream("j1")
    st.push("a", [1, 2], [0.0, 0.0])
    st.push("b", [7], [0.0])
    st.push("a", [1, 2, 3], [0.0, 0.0, 0.0])
    assert st.tokens_for("a") == [1, 2, 3]
    assert st.tokens_for("b") == [7]
    assert st.token_count() == 4


# ---------------------------------------------------------------------------
# streaming delivery against live engines
# ---------------------------------------------------------------------------
def test_prompt_job_streams_incrementally(tiny_setup):
    cfg, model, params = tiny_setup
    with _make_service(model, params) as svc:
        svc.register_tenant("cli")
        job = RolloutJob(kind="prompt", prompt=[1, 5, 7, 9],
                         max_new_tokens=24, temperature=0.0,
                         stop_tokens=())
        ticket = svc.submit("cli", job)
        assert ticket.state == JobState.QUEUED
        _tick_until(svc, lambda: ticket.done)
        assert ticket.state == JobState.DONE
        assert ticket.stream.closed and ticket.stream.finish_reason == "stop"
        _assert_stream_matches(ticket)
        # genuinely incremental: tokens arrived across several deliveries,
        # starting before the job finished
        assert len(ticket.stream.chunks()) >= 2
        assert ticket.stream.first_token_t < ticket.t_done
        assert svc.tenant("cli").stats["stream_tokens"] == \
            len(ticket.results[0].tokens)


def test_stream_across_pd_engine_handoff(tiny_setup):
    cfg, model, params = tiny_setup
    proxy = build_pd_proxy(model, params, max_slots=4, max_len=96, seed=7)
    with RolloutService(proxy) as svc:
        svc.register_tenant("cli")
        tickets = [svc.submit("cli", RolloutJob(
            kind="prompt", prompt=[1, 5, 7, 9 + i], max_new_tokens=20,
            temperature=0.0, stop_tokens=())) for i in range(3)]
        _tick_until(svc, lambda: all(t.done for t in tickets))
        assert proxy.handoffs >= 3, "prefill->decode handoff not exercised"
        for t in tickets:
            assert t.state == JobState.DONE
            _assert_stream_matches(t)


def test_stream_across_weight_sync_barrier(tiny_setup):
    cfg, model, params = tiny_setup
    with _make_service(model, params) as svc:
        svc.register_tenant("cli")
        ticket = svc.submit("cli", RolloutJob(
            kind="prompt", prompt=[1, 5, 7, 9], max_new_tokens=32,
            temperature=0.0, stop_tokens=()))
        for _ in range(3):
            svc.tick()                        # mid-stream
        n_before = ticket.stream.token_count()
        assert 0 < n_before < 32
        with svc.barrier():                   # suspend -> update -> resume
            svc.proxy.suspend()
            svc.proxy.update_all(params, version=1)   # re-prefills the
            svc.proxy.resume()                # in-flight slot (replays its
            #                                   cumulative token list)
        _tick_until(svc, lambda: ticket.done)
        assert ticket.state == JobState.DONE
        # the re-prefill replay collapsed into a no-op: no duplicates, no
        # gaps, and the stream still equals the final result exactly
        _assert_stream_matches(ticket)
        assert len(ticket.results[0].tokens) >= n_before


def test_abort_mid_stream_closes_aborted(tiny_setup):
    cfg, model, params = tiny_setup
    with _make_service(model, params) as svc:
        svc.register_tenant("cli")
        ticket = svc.submit("cli", RolloutJob(
            kind="prompt", prompt=[1, 5, 7, 9], max_new_tokens=64,
            temperature=0.0, stop_tokens=()))
        for _ in range(2):
            svc.tick()
        assert ticket.state == JobState.RUNNING
        assert ticket.stream.token_count() > 0
        svc.abort_job(ticket)
        _tick_until(svc, lambda: ticket.done)
        assert ticket.state == JobState.ABORTED
        assert ticket.stream.closed
        assert ticket.stream.finish_reason == JobState.ABORTED
        # the delivered prefix is exactly what the engine generated before
        # the cancel landed — gap-free, nothing fabricated after close
        [res] = ticket.results
        assert res.finish_reason == "aborted"
        assert ticket.stream.tokens_for(f"{ticket.job_id}.r0") == res.tokens
        assert 0 < len(res.tokens) < 64
        assert svc.tenant("cli").stats["aborted"] == 1


def test_abort_queued_job_never_launches(tiny_setup):
    cfg, model, params = tiny_setup
    with _make_service(model, params) as svc:
        svc.register_tenant("cli")
        ticket = svc.submit("cli", RolloutJob(kind="prompt", prompt=[1]))
        svc.abort_job(ticket)
        assert ticket.state == JobState.ABORTED and ticket.done
        svc.tick()
        assert svc.tenant("cli").stats["admitted"] == 0


# ---------------------------------------------------------------------------
# admission control: backpressure + stride-weighted QoS
# ---------------------------------------------------------------------------
def test_queue_backpressure_rejects_at_submit(tiny_setup):
    cfg, model, params = tiny_setup
    with _make_service(model, params) as svc:
        svc.register_tenant("cli", max_queue=2)
        t1 = svc.submit("cli", RolloutJob(kind="prompt", prompt=[1]))
        t2 = svc.submit("cli", RolloutJob(kind="prompt", prompt=[1]))
        t3 = svc.submit("cli", RolloutJob(kind="prompt", prompt=[1]))
        assert (t1.state, t2.state) == (JobState.QUEUED, JobState.QUEUED)
        assert t3.state == JobState.REJECTED and t3.done
        assert t3.stream.closed
        assert t3.stream.finish_reason == JobState.REJECTED
        assert svc.tenant("cli").stats["rejected"] == 1


def test_max_inflight_caps_admission(tiny_setup):
    cfg, model, params = tiny_setup
    with _make_service(model, params) as svc:
        svc.register_tenant("cli", max_inflight=2)
        tickets = [svc.submit("cli", RolloutJob(
            kind="prompt", prompt=[1, 5], max_new_tokens=16,
            temperature=0.0, stop_tokens=())) for _ in range(5)]
        svc.admit()
        states = [t.state for t in tickets]
        assert states.count(JobState.RUNNING) == 2
        assert states.count(JobState.QUEUED) == 3
        _tick_until(svc, lambda: all(t.done for t in tickets))
        assert all(t.state == JobState.DONE for t in tickets)


def test_global_admission_window_gates_on_stride(tiny_setup):
    """With a service-wide in-flight cap, overload queues at the service
    and the window's slots split by weight."""
    cfg, model, params = tiny_setup
    with _make_service(model, params) as svc:
        svc.max_inflight = 4
        svc.register_tenant("heavy", weight=3.0)
        svc.register_tenant("light", weight=1.0)
        mk = lambda: RolloutJob(kind="prompt", prompt=[1, 5],
                                max_new_tokens=8, temperature=0.0,
                                stop_tokens=())
        hv = [svc.submit("heavy", mk()) for _ in range(8)]
        lt = [svc.submit("light", mk()) for _ in range(8)]
        svc.admit()
        assert sum(t.state == JobState.RUNNING for t in hv + lt) == 4
        # the first window fills in stride order: h(1/3) l(1) h(2/3) h(1)
        assert sum(t.state == JobState.RUNNING for t in hv) == 3
        assert sum(t.state == JobState.RUNNING for t in lt) == 1
        _tick_until(svc, lambda: all(t.done for t in hv + lt))
        assert all(t.state == JobState.DONE for t in hv + lt)


def test_stride_shares_track_weights_under_overload(tiny_setup):
    """Two tenants saturate one small engine; admission order (and hence
    service order — the engine admits FIFO) must interleave 3:1."""
    cfg, model, params = tiny_setup
    with _make_service(model, params, max_slots=2) as svc:
        svc.register_tenant("heavy", weight=3.0)
        svc.register_tenant("light", weight=1.0)
        mk = lambda: RolloutJob(kind="prompt", prompt=[1, 5, 7],
                                max_new_tokens=8, temperature=0.0,
                                stop_tokens=())
        heavy = [svc.submit("heavy", mk()) for _ in range(24)]
        light = [svc.submit("light", mk()) for _ in range(24)]
        done = lambda: sum(t.done for t in heavy + light)
        _tick_until(svc, lambda: done() >= 16)
        first = sorted((t for t in heavy + light if t.done),
                       key=lambda t: t.t_done)[:16]
        n_heavy = sum(t.tenant == "heavy" for t in first)
        # stride order is exact; completion order can wobble by one
        # engine batch (max_slots) around it
        assert n_heavy >= 10, f"heavy got {n_heavy}/16 under a 3:1 weight"
        assert 16 - n_heavy >= 2, "light starved outright"
        _tick_until(svc, lambda: all(t.done for t in heavy + light))
        st = svc.stats()
        assert st["heavy"]["completed"] == 24
        assert st["light"]["completed"] == 24
        # stride bookkeeping: equal admissions cost light 3x the vtime
        assert st["light"]["vtime"] == pytest.approx(
            3 * st["heavy"]["vtime"])


def test_newcomer_tenant_gets_no_retroactive_burst(tiny_setup):
    cfg, model, params = tiny_setup
    with _make_service(model, params) as svc:
        a = svc.register_tenant("a")
        a.vtime = 7.0                      # a has been admitted for a while
        b = svc.register_tenant("b")
        assert b.vtime == 7.0              # joins at the live max


# ---------------------------------------------------------------------------
# the trainer is tenant #0: no private dispatch path remains
# ---------------------------------------------------------------------------
def test_runner_has_no_direct_pump_call():
    import ast

    import repro.core.scheduler as sched
    tree = ast.parse(inspect.getsource(sched))
    pumps = [n for n in ast.walk(tree)
             if isinstance(n, ast.Call)
             and isinstance(n.func, ast.Attribute)
             and n.func.attr == "pump"]
    assert not pumps, \
        "LiveRLRunner must reach the engines through RolloutService only"


def _make_runner(state, mode="sync", tasks=("game",), max_new=16,
                 max_len=320):
    cfg = get_config("tiny")
    model = Model(cfg, remat=False)
    opt = default_optimizer(1e-3)
    eng = InferenceEngine(model, state.params, max_slots=8,
                          max_len=max_len, seed=3)
    proxy = LLMProxy([EngineHandle(eng, "local")])
    return LiveRLRunner(
        RunnerConfig(batch_size=4, group_size=2, alpha=2, mode=mode,
                     tasks=tasks, max_new_tokens=max_new, temperature=0.0),
        proxy, state, jax.jit(make_grpo_train_step(model, opt)),
        ServerlessPlatform(), REWARD_FNS["format_bonus"], seq_len=max_len)


def _fresh_state():
    cfg = get_config("tiny")
    model = Model(cfg, remat=False)
    return init_train_state(model, jax.random.PRNGKey(0),
                            default_optimizer(1e-3))


@pytest.mark.slow
def test_second_tenant_rides_trainer_service():
    """An external client streams an env-group job through the SAME
    service the trainer trains through — and the trainer still trains."""
    runner = _make_runner(_fresh_state())
    try:
        svc = runner.service
        sink = []
        svc.register_tenant("client", tokenizer=runner.tok,
                            sink=sink.append, weight=1.0)
        job = RolloutJob(
            kind="env", tag="game",
            envs=[make_env("game", seed=91), make_env("game", seed=92)],
            seeds=[91, 92],
            policy=RolloutPolicy(max_new_tokens=12, temperature=0.0),
            stream=True)
        ticket = svc.submit("client", job)
        runner.run_steps(2)                     # trainer makes progress
        _tick_until(svc, lambda: ticket.done)
        assert ticket.state == JobState.DONE
        assert len(runner.history) == 2
        assert len(sink) == 2                   # both trajectories scored
        assert all(t.meta["state"] == "DONE" for t in sink)
        # streamed tokens tile gap-free for every request (turn) the
        # job's managers issued
        for rid in {c.request_id for c in ticket.stream.chunks()}:
            assert ticket.stream.tokens_for(rid)
    finally:
        runner.close()


@pytest.mark.slow
def test_stream_across_engine_kill_and_ft_recovery():
    """Engine kill mid-stream: supervised recovery re-homes BOTH the
    trainer's and the client tenant's in-flight requests, and the client's
    token stream stays monotonic and gap-free through the replay."""
    runner = _make_runner(_fresh_state(), max_new=64, max_len=640)
    svc = runner.service
    sup = FTSupervisor(runner, FTConfig(snapshot_every=1),
                       injector=FailureInjector(seed=3))
    try:
        sink = []
        client = svc.register_tenant("client", tokenizer=runner.tok,
                                     sink=sink.append)
        ticket = svc.submit("client", RolloutJob(
            kind="env", tag="game",
            envs=[make_env("game", seed=71), make_env("game", seed=72)],
            seeds=[71, 72],
            policy=RolloutPolicy(max_new_tokens=64, temperature=0.0),
            stream=True))
        runner._ensure_inflight()
        svc.admit()
        for _ in range(2):
            svc.tick()
        sup.last_snapshot = sup.snapshotter.capture(runner, 0)
        for _ in range(2):
            svc.tick()
        client_rids = {em._active_req for em in client.active
                       if em._active_req}
        assert client_rids, "no client request in flight at the kill"
        assert ticket.stream.token_count() > 0
        ev = sup.inject_and_recover("engine", 0)
        assert set(ev.lost_rids) & client_rids, \
            "the kill missed the client tenant's requests"
        assert ev.recovered
        _tick_until(svc, lambda: ticket.done, limit=5000)
        assert ticket.state == JobState.DONE
        assert ticket.stream.closed
        assert ticket.stream.finish_reason == "stop"
        for rid in {c.request_id for c in ticket.stream.chunks()}:
            ticket.stream.tokens_for(rid)       # asserts gap-free tiling
        assert len(sink) == 2
        assert all(t.meta["state"] == "DONE" for t in sink)
        assert not any(em.state == EMState.GENERATING
                       for em in client.active)
    finally:
        runner.close()
        sup.close()


# ---------------------------------------------------------------------------
# lifecycle: close is idempotent and exception-safe
# ---------------------------------------------------------------------------
def test_runner_close_is_idempotent():
    runner = _make_runner(_fresh_state(), mode="rollart")
    runner._start_rollout_worker()
    runner.close()
    assert runner.service._thread is None
    runner.close()                              # double-close: no-op
    with pytest.raises(RuntimeError, match="closed"):
        runner.service.start()                  # closed services stay down


def test_runner_close_after_worker_crash_returns_promptly():
    runner = _make_runner(_fresh_state(), mode="rollart")

    def boom():
        raise RuntimeError("injected tick crash")

    runner._tenant.pre_tick = boom
    runner._start_rollout_worker()
    deadline = time.monotonic() + 10
    while runner.service.error is None:
        assert time.monotonic() < deadline, "worker never crashed"
        time.sleep(0.005)
    t0 = time.monotonic()
    runner.close()                              # must not hang or raise
    runner.close()
    assert time.monotonic() - t0 < 5
    assert isinstance(runner.service.error, RuntimeError)


def test_service_close_is_reentrant_and_safe(tiny_setup):
    cfg, model, params = tiny_setup
    svc = _make_service(model, params)
    svc.start()
    svc.close()
    svc.close()
    assert svc._thread is None
    with pytest.raises(RuntimeError, match="closed"):
        svc.start()


# ---------------------------------------------------------------------------
# the hand-off: a waiting barrier gets the lock after the tick in flight
# ---------------------------------------------------------------------------
class _BusyProxy:
    """A stand-in proxy whose pump always reports work, so the service
    thread ticks back to back and would take its lock again at once. It
    counts the pumps that start while a barrier is announced (``late``),
    and can hold a pump at ``gate`` or make it raise."""
    handles = ()

    def __init__(self):
        self.svc = None
        self.pumps = self.late = 0
        self.entered = threading.Event()
        self.gate = threading.Event()
        self.gate.set()
        self.fail = False

    def pump(self):
        if self.svc._barriers:
            self.late += 1
        self.entered.set()
        self.gate.wait()
        if self.fail:
            raise RuntimeError("injected pump crash")
        self.pumps += 1
        time.sleep(0.0002)      # a dispatch's wait: the GIL goes, the lock not
        return 1


def _busy_service():
    proxy = _BusyProxy()
    svc = RolloutService(proxy)
    proxy.svc = svc
    return svc, proxy


def _await(pred, what, limit_s=10.0):
    """Hang guard only: every assertion counts ticks or parks."""
    deadline = time.monotonic() + limit_s
    while not pred():
        assert time.monotonic() < deadline, what
        time.sleep(0.0005)


def _await_ticks(svc, n):
    b0 = svc.beats
    _await(lambda: svc.beats >= b0 + n, "service thread not ticking")


def _await_parked(svc):
    _await(svc._parked.is_set, "service thread never parked")


def test_waiting_barrier_gets_the_lock_within_one_tick():
    svc, proxy = _busy_service()
    svc.start()
    try:
        for i in range(20):
            _await_ticks(svc, 3)
            late0 = proxy.late
            with svc.barrier():
                # at most the tick that raced the announcement ran after it
                assert proxy.late - late0 <= 1, \
                    f"barrier {i} waited {proxy.late - late0} ticks"
                _await_parked(svc)
                pumps = proxy.pumps
            _await_ticks(svc, 1)                  # the thread resumes
            assert proxy.pumps > pumps
            t = svc.timings()
            assert t["handoffs"] == i + 1         # one park per barrier
            assert t["handoff_s"] > 0
    finally:
        svc.close()
    assert svc.error is None


def test_barrier_stress_keeps_ticks_and_barriers_exclusive():
    """More barrier threads than cores against the ticking thread, with
    a short switch interval: every barrier excludes every tick, the
    announcement count returns to zero and the thread ticks on."""
    svc, proxy = _busy_service()
    holders, clashes, barriers = [0], [], 10
    pump = proxy.pump

    def exclusive_pump():
        if holders[0]:
            clashes.append(holders[0])
        return pump()

    proxy.pump = exclusive_pump

    def worker():
        for _ in range(barriers):
            with svc.barrier():
                holders[0] += 1
                time.sleep(0)
                holders[0] -= 1

    n = 2 * (os.cpu_count() or 2) + 2
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        svc.start()
        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(n)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
            assert not th.is_alive(), "a barrier hung"
        _await_ticks(svc, 2)
    finally:
        sys.setswitchinterval(interval)
        svc.close()
    assert not clashes and svc.error is None
    assert svc._barriers == 0 and not svc._announced.is_set()
    assert not svc._parked.is_set()
    assert svc.timings()["handoffs"] <= n * barriers


def test_barrier_without_service_thread_parks_nothing():
    """The cooperative sync path: no thread, so nothing parks."""
    svc, proxy = _busy_service()
    for _ in range(3):
        with svc.barrier():
            svc.tick()                            # a drain in the barrier
        svc.tick()
    assert proxy.pumps == 6 and proxy.late == 3
    t = svc.timings()
    assert (t["handoffs"], t["handoff_s"]) == (0, 0.0)
    assert svc._barriers == 0 and not svc._announced.is_set()
    assert not svc._parked.is_set()


def _barrier_thread(svc, release):
    got = threading.Event()

    def run():
        with svc.barrier():
            got.set()
            release.wait(10)

    th = threading.Thread(target=run, daemon=True)
    th.start()
    return th, got


@pytest.mark.parametrize("action", ["close", "pause"])
@pytest.mark.parametrize("state", ["waiting", "holding"])
def test_close_or_pause_with_a_barrier_waiting_returns_on_both_sides(
        state, action):
    """``waiting``: the barrier is blocked behind a tick in flight;
    ``holding``: it holds the lock and the thread is parked for it."""
    svc, proxy = _busy_service()
    svc.start()
    _await_ticks(svc, 2)
    release = threading.Event()
    if state == "waiting":
        release.set()
        proxy.entered.clear()
        proxy.gate.clear()
        assert proxy.entered.wait(10)             # the tick is in flight
        barrier, got = _barrier_thread(svc, release)
        _await(lambda: svc._barriers == 1, "barrier never announced")
        assert not got.is_set()
    else:
        barrier, got = _barrier_thread(svc, release)
        assert got.wait(10)
        _await_parked(svc)
    acting = threading.Thread(target=getattr(svc, action), daemon=True)
    acting.start()
    if state == "waiting":
        proxy.gate.set()
    elif action == "close":
        acting.join(5)                            # the parked thread wakes
        assert not acting.is_alive(), "close hung on a parked thread"
    release.set()
    for th in (acting, barrier):
        th.join(5)
        assert not th.is_alive(), f"{th.name} hung"
    assert got.is_set()
    if action == "pause":
        beats = svc.beats
        time.sleep(0.01)
        assert svc.beats == beats                 # paused: no tick
        svc.start()
        _await_ticks(svc, 1)
    svc.close()
    assert svc._thread is None and svc.error is None


def test_thread_crash_frees_a_waiting_barrier():
    svc, proxy = _busy_service()
    svc.start()
    _await_ticks(svc, 2)
    proxy.entered.clear()
    proxy.gate.clear()
    assert proxy.entered.wait(10)
    release = threading.Event()
    release.set()
    barrier, got = _barrier_thread(svc, release)
    _await(lambda: svc._barriers == 1, "barrier never announced")
    proxy.fail = True
    proxy.gate.set()                              # the tick in flight raises
    barrier.join(5)
    assert got.is_set() and not barrier.is_alive()
    assert isinstance(svc.error, RuntimeError)
    t0 = time.monotonic()
    svc.close()
    assert time.monotonic() - t0 < 5


def test_drain_reenters_a_barrier_while_the_thread_is_parked(tiny_setup):
    """Barrier-context callers (the FT snapshot hook) re-enter drain
    entry points and may nest a barrier: the lock stays reentrant and
    the thread parks once, until the outer barrier lets go."""
    cfg, model, params = tiny_setup
    with _make_service(model, params) as svc:
        svc.register_tenant("cli")
        ticket = svc.submit("cli", RolloutJob(
            kind="prompt", prompt=[1, 5, 7, 9], max_new_tokens=24,
            temperature=0.0, stop_tokens=()))
        svc.start()
        _await(lambda: ticket.stream.token_count() > 0, "no tokens")
        with svc.barrier():
            _await_parked(svc)
            n = ticket.stream.token_count()
            svc.drain_completions()
            svc.drain_rewards()
            svc.admit()
            with svc.barrier():
                svc.drain_tenant("cli", abort=False)   # pumps it to the end
            assert ticket.done and ticket.stream.token_count() >= n
            assert svc.timings()["handoffs"] == 0     # still parked
        _await(lambda: svc.timings()["handoffs"] == 1, "thread never resumed")
        _await_ticks(svc, 1)
        assert svc.timings()["handoffs"] == 1
        assert ticket.state == JobState.DONE
        _assert_stream_matches(ticket)
