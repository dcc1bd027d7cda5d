"""End-to-end behaviour tests for the RollArt system: live pipeline,
engine/proxy semantics, weight sync, resource plane, and the declarative
worker/cluster programming model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (Cluster, EngineHandle, LiveRLRunner, LLMProxy,
                        MooncakeStore, ResourceManager, RunnerConfig,
                        ServerlessPlatform, pull_params, push_params)
from repro.core.worker import (ActorGenCls, RewardCls,
                               hw_mapping, register, register_serverless)
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


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------
def test_engine_greedy_matches_manual(tiny_setup):
    cfg, model, params = tiny_setup
    eng = InferenceEngine(model, params, max_slots=2, max_len=96)
    eng.add_request(GenRequest(request_id="g", prompt=[1, 5, 7, 9],
                               max_new_tokens=5, temperature=0.0))
    eng.run_until_idle()
    res = eng.pop_result("g")
    cache = model.init_cache(1, 96)
    lg, cache = model.prefill(params, jnp.asarray([[1, 5, 7, 9]]), cache)
    out = []
    for t in range(5):
        nt = int(jnp.argmax(lg[0]))
        out.append(nt)
        lg, cache = model.decode_step(params, jnp.asarray([[nt]]), cache,
                                      jnp.asarray([4 + t]))
    assert res.tokens == out


def test_engine_abort_between_steps(tiny_setup):
    cfg, model, params = tiny_setup
    eng = InferenceEngine(model, params, max_slots=2, max_len=96)
    eng.add_request(GenRequest(request_id="a", prompt=[1, 2],
                               max_new_tokens=50, temperature=1.0))
    eng.step()
    eng.step()
    eng.abort("a")
    eng.run_until_idle()
    res = eng.pop_result("a")
    assert res.finish_reason == "aborted"
    assert len(res.tokens) < 50


def test_engine_weight_update_recomputes_cache(tiny_setup):
    """Protocol step (5): after update_params the in-flight trajectory
    continues under the NEW weights, exactly as a fresh prefill would."""
    cfg, model, params = tiny_setup
    params2 = model.init(jax.random.PRNGKey(42))
    eng = InferenceEngine(model, params, max_slots=1, max_len=96)
    eng.add_request(GenRequest(request_id="w", prompt=[1, 3, 5],
                               max_new_tokens=6, temperature=0.0))
    for _ in range(3):
        eng.step()
    prefix = list(eng._slots[0].tokens)
    eng.update_params(params2, version=1, recompute_caches=True)
    eng.run_until_idle()
    res = eng.pop_result("w")
    # replay: greedy continuation of `prefix` under params2
    cache = model.init_cache(1, 96)
    lg, cache = model.prefill(params2, jnp.asarray([prefix]), cache)
    expect = []
    pos = len(prefix)
    while len(prefix) - 3 + len(expect) < 6:
        nt = int(jnp.argmax(lg[0]))
        expect.append(nt)
        lg, cache = model.decode_step(params2, jnp.asarray([[nt]]), cache,
                                      jnp.asarray([pos]))
        pos += 1
    got_after_update = res.tokens[len(prefix) - 3:]
    assert got_after_update == expect[: len(got_after_update)]


# ---------------------------------------------------------------------------
# proxy (R1 routing + suspend/resume)
# ---------------------------------------------------------------------------
def test_proxy_affinity_routing(tiny_setup):
    cfg, model, params = tiny_setup
    e1 = InferenceEngine(model, params, max_slots=4, max_len=64, seed=1)
    e2 = InferenceEngine(model, params, max_slots=4, max_len=64, seed=2)
    proxy = LLMProxy([EngineHandle(e1, "H800"), EngineHandle(e2, "H20")],
                     hw_affinity={"frozenlake": "H800", "math": "H20",
                                  "default": "H20"})
    done = []
    for i, tag in enumerate(["frozenlake", "math", "frozenlake", "math"]):
        proxy.submit(GenRequest(request_id=f"r{i}", prompt=[1, 2],
                                max_new_tokens=3, tag=tag),
                     callback=done.append)
    while proxy.busy:
        proxy.pump()
    assert len(done) == 4
    assert proxy.routed_by_pool == {"H800": 2, "H20": 2}


def test_proxy_suspend_preserves_inflight(tiny_setup):
    cfg, model, params = tiny_setup
    e1 = InferenceEngine(model, params, max_slots=2, max_len=64)
    proxy = LLMProxy([EngineHandle(e1, "H20")])
    done = []
    proxy.submit(GenRequest(request_id="x", prompt=[1], max_new_tokens=8),
                 callback=done.append)
    proxy.pump()
    proxy.suspend()
    proxy.submit(GenRequest(request_id="y", prompt=[1], max_new_tokens=2),
                 callback=done.append)
    for _ in range(20):
        proxy.pump()
    assert [d.request_id for d in done] == ["x"]
    proxy.resume()
    while proxy.busy:
        proxy.pump()
    assert {d.request_id for d in done} == {"x", "y"}


# ---------------------------------------------------------------------------
# weight store
# ---------------------------------------------------------------------------
def test_mooncake_roundtrip(tiny_setup):
    cfg, model, params = tiny_setup
    store = MooncakeStore(bucket_mb=1)
    n = push_params(store, params, version=3)
    assert n > 0 and store.latest_version == 3
    pulled, v = pull_params(store, params)
    assert v == 3
    for a, b_ in zip(jax.tree.leaves(params), jax.tree.leaves(pulled)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_mooncake_latest_wins_and_bounded(tiny_setup):
    cfg, model, params = tiny_setup
    store = MooncakeStore(bucket_mb=1)
    for v in range(5):
        push_params(store, params, version=v)
    assert store.latest_version == 4
    assert set(store._buckets) == {3, 4}     # bounded retention


# ---------------------------------------------------------------------------
# resource plane + declarative data plane
# ---------------------------------------------------------------------------
def test_resource_binding_and_fallback():
    rm = ResourceManager({"H800": 2, "H20": 4, "CPU": 8})
    b1 = rm.bind("w1", "train", "H800", n_devices=2)
    assert b1 is not None and not b1.fallback
    b2 = rm.bind("w2", "generate", "H800", n_devices=2)
    assert b2 is not None and b2.fallback and b2.group.pool == "H20"
    assert rm.bind("w3", "train", "H800", n_devices=8) is None
    rm.release("w1")
    assert rm.available("H800") == 2


def test_cluster_decorators():
    class MyGen(ActorGenCls):
        DEFAULT_HW = "H20"

        @register(mode="execute_all")
        def ping(self, x):
            return (self.info.worker_id, x)

        @hw_mapping(hw_affinity={"frozenlake": "H800", "default": "H20"})
        def generate(self, prompt, tag_name="default"):
            return self.resource_type

    rm = ResourceManager({"H800": 2, "H20": 2})
    cluster = Cluster(rm, MyGen, num_workers=4)  # 2 on H20, fallback 2 H800
    pools = sorted(w.resource_type for w in cluster.workers)
    assert pools == ["H20", "H20", "H800", "H800"]
    out = cluster.ping(7)
    assert len(out) == 4 and all(x == 7 for _, x in out)
    assert cluster.generate("p", tag_name="frozenlake") == "H800"
    assert cluster.generate("p", tag_name="math") == "H20"
    cluster.shutdown()


def test_serverless_registration():
    class MyReward(RewardCls):
        @register_serverless(attribute="reward_proxy",
                             serverless_url="fc://test/reward")
        def compute_rewards(self, traj):
            return self.reward_proxy(traj)

    sls = ServerlessPlatform()
    sls.deploy("fc://test/reward", lambda traj: sum(traj))
    rm = ResourceManager({"Serverless": 10})
    cluster = Cluster(rm, MyReward, num_workers=1, serverless=sls)
    assert cluster.compute_rewards([1, 2, 3]) == [6]
    assert sls.stats.invocations == 1
    cluster.shutdown()


# ---------------------------------------------------------------------------
# full live pipeline (the paper's six-step protocol, real compute)
# ---------------------------------------------------------------------------
def test_live_pipeline_two_steps(tiny_setup):
    cfg, model, params = tiny_setup
    opt = default_optimizer(1e-3)
    state = init_train_state(model, jax.random.PRNGKey(0), opt)
    eng = InferenceEngine(model, state.params, max_slots=8, max_len=256,
                          seed=3)
    proxy = LLMProxy([EngineHandle(eng, "H20")])
    with LiveRLRunner(
            RunnerConfig(batch_size=4, group_size=2, alpha=1,
                         tasks=("game",), max_new_tokens=12),
            proxy, state, jax.jit(make_grpo_train_step(model, opt)),
            ServerlessPlatform(), format_bonus_reward,
            seq_len=256) as runner:
        barriers = []
        runner.barrier_hook = lambda r, step: barriers.append(
            (r.version, eng.stats()["weight_version"],
             all(a.unsafe_buffer_pointer() == b.unsafe_buffer_pointer()
                 for a, b in zip(jax.tree.leaves(eng.params),
                                 jax.tree.leaves(r.state.params)))))
        hist = runner.run_steps(2)
        assert len(hist) == 2
        assert runner.version == 2
        assert all(np.isfinite(h.loss) for h in hist)
        assert runner.serverless.stats.invocations >= 8
        # the engine sits on the trainer's device: at every barrier it is
        # at the runner's version, holding the trainer's own arrays, and
        # the store holds the v0 publication alone
        assert barriers == [(0, 0, True), (1, 1, True)]
        assert runner.store.log.pushes == 1
