"""The weight hand-off at the barrier. An engine with no mesh on the
trainer's own device takes the trainer's arrays as they are (the device
path: no host copy, no device copy, no per-step push); every other engine
(a TP group, an engine on another device) pulls each version from the
store, which the runner then publishes every step.

The cases with an engine on another device need >= 2 host devices; run
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set below
when this module is the first jax importer, e.g. a standalone run).
"""
import os
import sys

if "jax" not in sys.modules:      # must precede the first jax import
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (EngineHandle, LiveRLRunner, LLMProxy, RunnerConfig,
                        ServerlessPlatform)
from repro.launch.mesh import make_group_mesh
from repro.models import Model
from repro.obs import spans
from repro.rewards.rule_based import format_bonus_reward
from repro.rl.engine import InferenceEngine
from repro.rl.trainer import (default_optimizer, init_train_state,
                              make_grpo_train_step)

MODEL = Model(get_config("tiny"), remat=False)

needs_two_devices = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _runner(placements, mode="rollart"):
    """A seeded runner with one engine per entry of ``placements``:
    ``None`` builds it over the trainer's own arrays, ``"mesh"`` gives it
    a one-device group mesh, a device builds it over a copy there."""
    opt = default_optimizer(1e-3)
    state = init_train_state(MODEL, jax.random.PRNGKey(0), opt)
    handles = []
    for i, where in enumerate(placements):
        mesh = (make_group_mesh(jax.devices()[:1]) if where == "mesh"
                else None)
        params = (jax.device_put(state.params, where)
                  if isinstance(where, jax.Device) else state.params)
        eng = InferenceEngine(MODEL, params, max_slots=8, max_len=256,
                              seed=3 + i, mesh=mesh)
        handles.append(EngineHandle(eng, "H20", f"e{i}"))
    return LiveRLRunner(
        RunnerConfig(mode=mode, batch_size=4, group_size=2, alpha=1,
                     tasks=("game",), max_new_tokens=12),
        LLMProxy(handles), state, jax.jit(make_grpo_train_step(MODEL, opt)),
        ServerlessPlatform(), format_bonus_reward, seq_len=256)


def _same_arrays(a, b) -> bool:
    return all(x.unsafe_buffer_pointer() == y.unsafe_buffer_pointer()
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _record_barriers(runner):
    """At every barrier, once the engines have their weights: the
    runner's version, each engine's, and whether each engine holds the
    trainer's own arrays."""
    seen = []

    def hook(r, step):
        seen.append((r.version,
                     [h.engine.stats()["weight_version"]
                      for h in r.proxy.handles],
                     [_same_arrays(h.engine.params, r.state.params)
                      for h in r.proxy.handles]))
    runner.barrier_hook = hook
    return seen


def _syncs(runner):
    return [(st["local_syncs"], st["store_syncs"])
            for st in (h.engine.stats() for h in runner.proxy.handles)]


def test_engine_on_the_trainers_device_takes_its_arrays(monkeypatch):
    runner = _runner([None])        # publishes v0
    seen = _record_barriers(runner)
    opened = []
    real_span = spans.span
    monkeypatch.setattr(spans, "span",
                        lambda name: opened.append(name) or real_span(name))
    with runner:
        hist = runner.run_steps(3)
    assert [version for version, _, _ in seen] == [0, 1, 2]
    for version, versions, same in seen:
        assert versions == [version] and same == [True]
    assert _syncs(runner) == [(2, 0)]
    assert [h.push_wait_s for h in hist] == [0.0, 0.0, 0.0]
    # the store holds the v0 publication alone
    assert runner.store.log.pushes == 1
    assert runner.store.latest_version == 0
    assert "repro.weights.local" in opened
    assert "repro.weights.pull" not in opened
    assert "repro.weights.push" not in opened


def test_deleted_trainer_leaf_is_refused():
    """A donated trainer state leaves deleted leaves behind: the device
    path refuses them at the barrier, before any dispatch reads them."""
    params = MODEL.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(MODEL, params, max_slots=2, max_len=64)
    trained = jax.tree.map(lambda x: x + 0, params)
    jax.tree.leaves(trained)[0].delete()
    assert eng.shares_device(trained)
    with pytest.raises(RuntimeError, match="deleted leaf"):
        eng.update_params(trained, 1)
    assert eng.stats()["weight_version"] == 0
    assert _same_arrays(eng.params, params)


@pytest.mark.parametrize("where", [
    "mesh", pytest.param(1, marks=needs_two_devices, id="other-device")])
def test_engine_off_the_trainers_device_pulls_every_version(where):
    """A mixed plane: the engine on the trainer's device takes its arrays,
    the other (a one-device TP group, or an engine on another device)
    pulls each version from the store, which the runner publishes after
    every step."""
    off = jax.devices()[where] if isinstance(where, int) else where
    runner = _runner([None, off])
    seen = _record_barriers(runner)
    with runner:
        runner.run_steps(3)
    assert [version for version, _, _ in seen] == [0, 1, 2]
    for version, versions, same in seen:
        assert versions == [version, version] and same[0]
    assert _syncs(runner) == [(2, 0), (0, 2)]
    assert runner.store.latest_version == 3
    assert runner.store.log.pushes == 4
    if isinstance(where, int):
        # the store path places each version on the engine's own device
        assert {d for leaf in jax.tree.leaves(
            runner.proxy.handles[1].engine.params)
            for d in leaf.sharding.device_set} == {off}


@needs_two_devices
def test_device_path_trains_what_the_store_path_trains():
    """The same seeded schedule, once with the engine on the trainer's
    device and once on another device, which takes the store path: the
    trained batches (tokens, rewards) and the losses are identical."""
    runs = []
    for where in (None, jax.devices()[1]):
        runner = _runner([where], mode="sync")
        trained = []

        def step(state, batch, runner=runner, trained=trained,
                 fn=runner.train_step_fn):
            trained.append(
                ([(tuple(t.tokens), t.reward)
                  for t in runner.last_batch],
                 np.asarray(batch["tokens"]),
                 np.asarray(batch["advantages"])))
            return fn(state, batch)
        runner.train_step_fn = step
        with runner:
            losses = [h.loss for h in runner.run_steps(3)]
        runs.append((trained, losses, _syncs(runner)))
    (trained0, losses0, syncs0), (trained1, losses1, syncs1) = runs
    assert (syncs0, syncs1) == ([(2, 0)], [(0, 2)])
    assert len(trained0) == len(trained1) == 3
    for (trajs0, tok0, adv0), (trajs1, tok1, adv1) in zip(trained0,
                                                          trained1):
        assert trajs0 == trajs1
        np.testing.assert_array_equal(tok0, tok1)
        np.testing.assert_array_equal(adv0, adv1)
    assert losses0 == losses1
