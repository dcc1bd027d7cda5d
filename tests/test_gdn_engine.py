"""A Gated DeltaNet + MoE stack (Qwen3-Next, reduced) on the inference
engine: bucketed prefill, the weight-sync barrier's recompute, PD handoff
of a slot, and the decode block's expert counters."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Model
from repro.rl.engine import GenRequest, InferenceEngine

PROMPT = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2]   # 17: bucket 32


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3-next-80b-a3b").reduced().with_(num_layers=4)
    model = Model(cfg, remat=False)
    return model, model.init(jax.random.PRNGKey(0))


def engine(setup, **kw):
    model, params = setup
    return InferenceEngine(model, params, max_slots=2, max_len=96, seed=5,
                           steps_per_dispatch=4, **kw)


def generate(eng, max_new=24, rid="r"):
    out = []
    eng.on_finish = out.append
    eng.add_request(GenRequest(rid, list(PROMPT), max_new_tokens=max_new,
                               temperature=0.0))
    eng.run_until_idle()
    return out[0]


def test_gdn_stack_prefills_in_buckets(setup):
    """Padding a prompt to its bucket changes nothing the model returns:
    greedy streams equal the unpadded engine's."""
    padded, exact = engine(setup), engine(setup, bucketed_prefill=False)
    assert padded._bucketed_prefill and not exact._bucketed_prefill
    a, b = generate(padded), generate(exact)
    assert a.tokens == b.tokens
    np.testing.assert_allclose(a.logprobs, b.logprobs, atol=1e-4)


def test_barrier_recompute_rebuilds_the_state(setup):
    """A weight sync mid-generation (same weights, new version) rebuilds
    the slot's DeltaNet state and KV from its tokens; the stream goes on
    as if nothing happened."""
    model, params = setup
    ref = generate(engine(setup))
    eng = engine(setup)
    out = []
    eng.on_finish = out.append
    eng.add_request(GenRequest("r", list(PROMPT), max_new_tokens=24,
                               temperature=0.0))
    eng.step()
    eng.step()
    eng.update_params(params, version=1)
    assert eng.stats()["recomputes"] == 1
    eng.run_until_idle()
    assert out[0].tokens == ref.tokens
    np.testing.assert_allclose(out[0].logprobs, ref.logprobs, atol=1e-4)


def test_gdn_slot_survives_extract_and_inject(setup):
    """A slot's DeltaNet state and KV, extracted after a partial
    generation and injected into another engine, continue the stream."""
    ref = generate(engine(setup))
    src = engine(setup)
    src.add_request(GenRequest("r", list(PROMPT), max_new_tokens=24,
                               temperature=0.0))
    src.step()
    [handoff] = src.snapshot_slots()
    assert {"S", "conv"} <= set(handoff.cache[0])
    dst = engine(setup)
    out = []
    dst.on_finish = out.append
    dst.inject(handoff)
    dst.run_until_idle()
    assert out[0].tokens == ref.tokens
    np.testing.assert_allclose(out[0].logprobs, ref.logprobs, atol=1e-4)


def test_decode_block_counts_held_experts(setup):
    """The engine sums each decode block's expert counters: held experts
    touched (at most held x layers a scanned step) and assignments here
    (at most top-k x layers a decoded token)."""
    model, _ = setup
    cfg = model.cfg
    eng = engine(setup)
    generate(eng)
    st = eng.stats()
    steps = st["decode_dispatches"] * eng.steps_per_dispatch
    assert 0 < st["moe_experts_touched"] <= \
        cfg.num_experts * cfg.num_layers * steps
    assert st["moe_experts_touched"] <= st["moe_tokens_here"] <= \
        cfg.top_k * cfg.num_layers * st["decode_tokens"]
