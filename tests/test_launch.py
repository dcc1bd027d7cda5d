"""Launch/runtime layer: device labels, the compile-cache placement, the
runner the train launcher and ``chip_smoke.py`` share, and where a
single-device engine keeps synced weights."""
import types

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.hardware import REGISTRY, device_pool
from repro.launch import compile_cache
from repro.launch import train as T
from repro.models import Model
from repro.rl.engine import InferenceEngine
from repro.rl.trainer import (default_optimizer, init_train_state,
                              make_grpo_train_step)


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize("platform,kind,pool", [
    ("cpu", "cpu", "CPU"),
    ("tpu", "TPU v5 lite", "TPUv5e"),
    ("tpu", "TPU v5", "TPUv5p"),
])
def test_device_pool_names_registry_entry(platform, kind, pool):
    assert device_pool(_device(platform, kind)) == pool
    assert pool in REGISTRY


@pytest.mark.parametrize("platform,kind", [
    ("tpu", "TPU v7x"),          # a TPU with no spec never borrows one
    ("gpu", "NVIDIA H100 80GB HBM3"),
])
def test_device_pool_rejects_unknown_kind(platform, kind):
    with pytest.raises(ValueError, match="no HardwareSpec"):
        device_pool(_device(platform, kind))


@pytest.mark.parametrize("env_dir,group", [(True, 1), (False, 1), (True, 2)])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir, group):
    from jax.experimental.compilation_cache import compilation_cache as cc
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(compile_cache.CHECKOUT / ".jax_cache")
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs,
              jax.config.jax_enable_compilation_cache)
    try:
        # nothing compiles in between, so no entry is written anywhere
        got = compile_cache.enable_compile_cache(group)
        if group > 1:       # TP engine groups keep the cache off
            assert got is None and not jax.config.jax_enable_compilation_cache
            assert jax.config.jax_compilation_cache_dir == before[0]
        else:
            assert got == want
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])
        jax.config.update("jax_enable_compilation_cache", before[2])
        cc.reset_cache()


def test_build_runner_labels_engines_with_their_device():
    model = Model(get_config("tiny"), remat=False)
    opt = default_optimizer()
    state = init_train_state(model, jax.random.PRNGKey(0), opt)
    args = T.build_parser().parse_args(["--batch", "4", "--group", "2"])
    runner = T.build_runner(args, model, state,
                            jax.jit(make_grpo_train_step(model, opt)))
    with runner:
        [handle] = runner.proxy.handles
        assert handle.pool == device_pool(jax.devices()[0]) == "CPU"
        assert runner.cfg.batch_size == 4 and runner.cfg.group_size == 2
    runner.proxy.release_bindings()


def test_synced_params_live_on_device():
    model = Model(get_config("tiny"), remat=False)
    params = model.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, max_slots=2, max_len=32)
    # the weight store hands out host arrays
    eng.update_params(jax.tree.map(np.asarray, params), 1)
    assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(eng.params))
    assert "host" not in eng.param_device_bytes()
