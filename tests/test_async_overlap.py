"""Tests for the genuinely-asynchronous live runner (train/rollout overlap)
and the concurrency bugfixes that ride along:

- SampleBuffer under concurrent put/get_batch;
- threaded rollout worker vs cooperative pump greedy-parity;
- async-reward submission-order buffering;
- EnvManager.abort on a non-GENERATING manager fires on_complete;
- update_params/update_all no-op on weight-version match;
- LLMProxy.abort ignores unknown/finished ids; per-step metric deltas;
- ServerlessPlatform thread-safety + max_concurrency + payload accounting;
- live one_off trains on the previous iteration's batch.
"""
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (EngineHandle, LiveRLRunner, LLMProxy, RunnerConfig,
                        ServerlessPlatform)
from repro.core.buffer import SampleBuffer
from repro.core.envmanager import EMState, EnvManager
from repro.core.serverless import ServerlessConfig
from repro.data.pipeline import Trajectory
from repro.models import Model
from repro.rewards.rule_based import format_bonus_reward
from repro.rl.engine import GenRequest, InferenceEngine
from repro.rl.trainer import (default_optimizer, init_train_state,
                              make_grpo_train_step)


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = get_config("tiny")
    model = Model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _make_runner(model, mode, **cfg_kw):
    opt = default_optimizer(1e-3)
    state = init_train_state(model, jax.random.PRNGKey(0), opt)
    eng = InferenceEngine(model, state.params, max_slots=8, max_len=256,
                          seed=3)
    proxy = LLMProxy([EngineHandle(eng, "H20")])
    kw = dict(batch_size=4, group_size=2, alpha=1, tasks=("game",),
              max_new_tokens=12, temperature=0.0)
    kw.update(cfg_kw)
    return LiveRLRunner(
        RunnerConfig(mode=mode, **kw), proxy, state,
        jax.jit(make_grpo_train_step(model, opt)),
        ServerlessPlatform(), format_bonus_reward, seq_len=256)


def _traj(i, sv=0):
    return Trajectory(traj_id=f"t{i}", task="math", tokens=[1, 2],
                      loss_mask=[0, 1], logprobs=[0.0, -1.0],
                      start_version=sv)


# ---------------------------------------------------------------------------
# SampleBuffer under concurrency
# ---------------------------------------------------------------------------
def test_buffer_concurrent_put_get():
    buf = SampleBuffer(alpha=100)
    n_producers, per_producer, batch = 4, 25, 10
    total = n_producers * per_producer

    def produce(base):
        for i in range(per_producer):
            buf.put(_traj(base * per_producer + i))
            if i % 7 == 0:
                time.sleep(0.001)

    got = []

    def consume():
        for _ in range(total // batch):
            got.extend(buf.get_batch(batch, timeout=10))

    threads = [threading.Thread(target=produce, args=(b,))
               for b in range(n_producers)]
    threads.append(threading.Thread(target=consume))
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=20)
    assert len(got) == total
    assert len({t.traj_id for t in got}) == total      # no dup, no loss
    assert buf.size() == 0
    assert buf.total_consumed == total


# ---------------------------------------------------------------------------
# async reward: submission-order buffering
# ---------------------------------------------------------------------------
def test_async_reward_preserves_submission_order(tiny_setup):
    cfg, model, params = tiny_setup
    runner = _make_runner(model, "rollart")
    try:
        sls = runner.serverless
        gate = threading.Event()
        sls.deploy("fc://t/slow", lambda p: (gate.wait(5), 1.0)[1])
        sls.deploy("fc://t/fast", lambda p: 2.0)
        t_slow, t_fast = _traj("slow"), _traj("fast")
        runner._pending_rewards.append(
            [t_slow, {}, sls.invoke_async("fc://t/slow", {}), 0])
        runner._pending_rewards.append(
            [t_fast, {}, sls.invoke_async("fc://t/fast", {}), 0])
        deadline = time.monotonic() + 5
        while not runner._pending_rewards[1][2].done():
            assert time.monotonic() < deadline
            time.sleep(0.005)
        # the LATER future resolved first, but the head gates the drain
        assert runner._drain_rewards() == 0
        assert runner.buffer.size() == 0
        gate.set()
        assert runner._drain_rewards(block=True) == 2
        batch = runner.buffer.try_get_batch(2)
        assert [t.traj_id for t in batch] == ["tslow", "tfast"]
        assert [t.reward for t in batch] == [1.0, 2.0]
    finally:
        runner.close()


# ---------------------------------------------------------------------------
# EnvManager.abort completion semantics
# ---------------------------------------------------------------------------
class _DummyEnv:
    TASK = "dummy"


def test_envmanager_abort_idle_fires_on_complete():
    done = []
    em = EnvManager(_DummyEnv(), proxy=None, tag="dummy",
                    on_complete=done.append)
    em.abort()
    assert em.state is EMState.ABORTED
    assert done == [em]            # the runner can reap it from `active`
    em.abort()                     # idempotent: no double completion
    assert done == [em]


def test_envmanager_abort_completed_is_noop():
    done = []
    em = EnvManager(_DummyEnv(), proxy=None, tag="dummy",
                    on_complete=done.append)
    em.state = EMState.DONE
    em.abort()
    assert em.state is EMState.DONE and done == []


# ---------------------------------------------------------------------------
# weight-version no-op (protocol step (3)/(5))
# ---------------------------------------------------------------------------
def test_update_params_version_match_is_noop(tiny_setup):
    cfg, model, params = tiny_setup
    # max_new_tokens > 3 macro-steps * steps_per_dispatch so the request
    # is still mid-flight when update_params fires
    n_new = 30
    ref = InferenceEngine(model, params, max_slots=2, max_len=96)
    ref.add_request(GenRequest(request_id="r", prompt=[1, 5, 7],
                               max_new_tokens=n_new, temperature=0.0))
    ref.run_until_idle()
    expect = ref.pop_result("r").tokens

    eng = InferenceEngine(model, params, max_slots=2, max_len=96)
    eng.add_request(GenRequest(request_id="r", prompt=[1, 5, 7],
                               max_new_tokens=n_new, temperature=0.0))
    for _ in range(3):
        eng.step()
    eng.update_params(params, version=0)       # same version: must no-op
    assert eng.recomputes == 0
    eng.run_until_idle()
    assert eng.pop_result("r").tokens == expect

    params2 = model.init(jax.random.PRNGKey(7))
    eng.add_request(GenRequest(request_id="r2", prompt=[1, 5, 7],
                               max_new_tokens=n_new, temperature=0.0))
    eng.step()
    eng.update_params(params2, version=1)      # real update: recomputes
    assert eng.weight_version == 1
    assert eng.recomputes == 1
    eng.run_until_idle()


# ---------------------------------------------------------------------------
# proxy abort accounting
# ---------------------------------------------------------------------------
def test_proxy_abort_unknown_and_finished_ids_not_counted(tiny_setup):
    cfg, model, params = tiny_setup
    eng = InferenceEngine(model, params, max_slots=2, max_len=96)
    proxy = LLMProxy([EngineHandle(eng, "H20")])
    proxy.abort("never-submitted")
    assert proxy.aborted == 0
    done = []
    proxy.submit(GenRequest(request_id="a", prompt=[1, 2],
                            max_new_tokens=40), callback=done.append)
    proxy.pump()
    proxy.abort("a")
    assert proxy.aborted == 1
    while proxy.busy:
        proxy.pump()
    assert done and done[0].finish_reason == "aborted"
    proxy.abort("a")               # already finished: not an abort
    assert proxy.aborted == 1


# ---------------------------------------------------------------------------
# ServerlessPlatform concurrency
# ---------------------------------------------------------------------------
def test_serverless_thread_safety_and_max_concurrency():
    sls = ServerlessPlatform(ServerlessConfig(max_concurrency=2))
    peak = {"n": 0, "cur": 0}
    peak_lock = threading.Lock()

    def fn(payload):
        with peak_lock:
            peak["cur"] += 1
            peak["n"] = max(peak["n"], peak["cur"])
        time.sleep(0.02)
        with peak_lock:
            peak["cur"] -= 1
        return 1.0

    sls.deploy("fc://t/f", fn)
    futs = [sls.invoke_async("fc://t/f", {"tokens": [1, 2, 3], "text": "x"})
            for _ in range(8)]
    assert all(f.result(timeout=10) == 1.0 for f in futs)
    assert sls.stats.invocations == 8
    assert peak["n"] <= 2                      # admission control held
    assert sls.stats.peak_instances <= 2
    assert sls.stats.payload_bytes > 0         # live payloads accounted
    assert sls.stats.total_exec_s > 0


# ---------------------------------------------------------------------------
# threaded vs cooperative greedy parity + overlap + per-step deltas
# ---------------------------------------------------------------------------
def _batch_fingerprint(trajs):
    return sorted((t.task, tuple(t.tokens), round(t.reward, 6))
                  for t in trajs)


def test_threaded_pump_matches_cooperative_greedy(tiny_setup):
    cfg, model, params = tiny_setup
    with _make_runner(model, "sync") as sync_runner:
        sync_hist = sync_runner.run_steps(1)
        sync_batch = _batch_fingerprint(sync_runner.last_batch)
    with _make_runner(model, "rollart") as roll_runner:
        roll_hist = roll_runner.run_steps(1)
        roll_batch = _batch_fingerprint(roll_runner.last_batch)
    assert roll_batch == sync_batch
    assert np.isclose(roll_hist[0].loss, sync_hist[0].loss, atol=1e-5)
    # the synchronous baseline never decodes while train_step runs
    assert sync_hist[0].decode_during_train == 0


def test_one_off_trains_on_previous_batch_with_overlap(tiny_setup):
    cfg, model, params = tiny_setup
    with _make_runner(model, "one_off") as runner:
        eng = runner.proxy.handles[0].engine
        barriers = []
        runner.barrier_hook = lambda r, step: barriers.append(
            (r.version, eng.stats()["weight_version"],
             all(a.unsafe_buffer_pointer() == b.unsafe_buffer_pointer()
                 for a, b in zip(jax.tree.leaves(eng.params),
                                 jax.tree.leaves(r.state.params)))))
        hist = runner.run_steps(3)
        assert [h.batch_fetched_step for h in hist] == [-1, 0, 1]
        assert all(h.batch_fetched_step < h.step for h in hist)
        # trained batches predate the version being trained
        assert all(h.batch_max_version < runner.version for h in hist)
        # overlap is real: engines decoded while train_step ran
        assert sum(h.decode_during_train for h in hist) > 0
        # per-step metric deltas sum to the cumulative totals
        assert sum(h.evicted for h in hist) == runner.buffer.total_evicted
        assert sum(h.aborted for h in hist) == runner.proxy.aborted
        assert all(np.isfinite(h.loss) for h in hist)
        # the engine sits on the trainer's device: at every barrier it is
        # at the runner's version, holding the trainer's own arrays, and
        # the store holds the v0 publication alone
        assert barriers == [(0, 0, True), (1, 1, True), (2, 2, True)]
        assert runner.store.log.pushes == 1
    with pytest.raises(RuntimeError):      # closed runner fails fast
        runner.run_steps(1)
