"""Compiles for a described TPU v5e, with no chip attached: the Pallas
kernels at Qwen3-8B widths (``interpret=False``, so each must lower to a
``tpu_custom_call``), and the GRPO train step plus the engine's K-step
``decode_block`` at the chip-smoke config and at Qwen3-Next's one-chip
share, from ``jax.eval_shape`` shapes. The train step must fit one chip's HBM next to the engine's own
params copy. Nothing runs: these are the chip compiler's verdicts only.

The topology is described inside a fixture, never at import, so every
xdist worker collects the same tests and only the worker given this file
loads the TPU library. The persistent compile cache is off around the
compiles: an entry written without a chip cannot be read back.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.qwen3_8b import CHIP_SMOKE, CONFIG as QWEN3_8B
from repro.configs.qwen3_next_80b_a3b import CHIP_SHARE as QWEN3_NEXT
from repro.kernels.decode_attention import decode_attention, \
    ragged_paged_decode
from repro.kernels.flash_attention import flash_attention
from repro.models import Model
from repro.rl.sampling import sample_mixed
from repro.rl.trainer import (default_optimizer, grpo_batch_spec,
                              init_train_state, make_grpo_train_step)

# what XLA lets one v5e program use of the chip's 16 GB
V5E_HBM_LIMIT = 15.75 * 2 ** 30
# the live loop's shapes (launch/train.build_runner, RunnerConfig)
SLOTS, MAX_LEN, K = 8, 640, 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _kernel_case(name, sh):
    bf = jnp.bfloat16
    H, kvH, hd = QWEN3_8B.num_heads, QWEN3_8B.num_kv_heads, QWEN3_8B.head_dim
    q = jax.ShapeDtypeStruct((SLOTS, H, hd), bf, sharding=sh)
    lens = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=sh)
    if name == "decode_attention":
        kv = jax.ShapeDtypeStruct((SLOTS, kvH, MAX_LEN, hd), bf, sharding=sh)
        return (lambda q, k, v, n: decode_attention(
            q, k, v, n, block_k=128, interpret=False)), (q, kv, kv, lens)
    if name == "ragged_paged_decode":
        page = 16
        pages = MAX_LEN // page
        pool = jax.ShapeDtypeStruct((SLOTS * pages + 1, kvH, page, hd), bf,
                                    sharding=sh)
        tbl = jax.ShapeDtypeStruct((SLOTS, pages), jnp.int32, sharding=sh)
        return (lambda q, k, v, t, n: ragged_paged_decode(
            q, k, v, t, n, interpret=False)), (q, pool, pool, tbl, lens)
    qs = jax.ShapeDtypeStruct((1, H, 512, hd), bf, sharding=sh)
    kvs = jax.ShapeDtypeStruct((1, kvH, 512, hd), bf, sharding=sh)
    return (lambda q, k, v: flash_attention(q, k, v, interpret=False)), \
        (qs, kvs, kvs)


@pytest.mark.parametrize("name", ["decode_attention", "ragged_paged_decode",
                                  "flash_attention"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_grpo_train_step_fits_one_v5e(one_chip):
    _train_step_fits(one_chip, CHIP_SMOKE, SLOTS)


def test_qwen3_next_grpo_train_step_fits_one_v5e(one_chip):
    """The chunked delta rule and the dropless expert matmuls, forward
    and backward, at batch 32 (8 x group 4) of 640 positions."""
    _train_step_fits(one_chip, QWEN3_NEXT, 4 * SLOTS)


def _train_step_fits(one_chip, cfg, rows):
    model = Model(cfg, remat=False)
    opt = default_optimizer()
    state = _on(one_chip, jax.eval_shape(
        lambda: init_train_state(model, jax.random.PRNGKey(0), opt)))
    batch = _on(one_chip, grpo_batch_spec(cfg, rows, MAX_LEN))
    # un-donated, exactly as the launcher jits it
    mem = jax.jit(make_grpo_train_step(model, opt)).lower(
        state, batch).compile().memory_analysis()
    engine_params = sum(x.size * x.dtype.itemsize
                        for x in jax.tree.leaves(state.params))
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + engine_params)
    assert total < V5E_HBM_LIMIT, (
        f"train step {total / 2 ** 30:.2f} GiB with the engine's params "
        f"copy exceeds {V5E_HBM_LIMIT / 2 ** 30} GiB")


def test_decode_block_compiles_for_v5e(one_chip):
    _decode_block_compiles(one_chip, CHIP_SMOKE)


def test_qwen3_next_decode_block_compiles_for_v5e(one_chip):
    """Three DeltaNet steps, a gated attention and four grouped expert
    matmuls a scanned step."""
    _decode_block_compiles(one_chip, QWEN3_NEXT)


def _decode_block_compiles(one_chip, cfg):
    model = Model(cfg, remat=False)
    params = _on(one_chip, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))))
    cache = _on(one_chip, jax.eval_shape(
        lambda: model.init_cache(SLOTS, MAX_LEN)))
    key = _on(one_chip, jax.eval_shape(lambda: jax.random.PRNGKey(0)))

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def block(params, tokens, cache, positions, key, temps, stop_ids,
              budgets):
        keys = jax.random.split(key, K)
        return model.decode_block(params, tokens, cache, positions, keys,
                                  temps, stop_ids, budgets,
                                  sample_fn=sample_mixed)

    compiled = jax.jit(block, donate_argnums=(2,)).lower(
        params, spec((SLOTS, 1)), cache, spec((SLOTS,)), key,
        spec((SLOTS,), jnp.float32), spec((SLOTS, 4)),
        spec((SLOTS,))).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 0      # the donated cache is in place
