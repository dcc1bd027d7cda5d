"""The chip benchmark's float32 Qwen3 reference (benchmarks/chip/reference.py)
against the system's model, at a tiny Qwen3 shape on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import reference as R
from repro.configs.base import ModelConfig
from repro.models import Model

CFG = {"model_type": "qwen3", "num_hidden_layers": 2, "hidden_size": 64,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "intermediate_size": 96, "vocab_size": 128, "rope_theta": 1000000,
       "rms_norm_eps": 1e-6}
SEED = 2 ** 31 + 11

# The model's prefill + cached decode in float32 against the reference's
# teacher-forced forward. Both compute in float32; what differs is the
# order of the sums (blocked attention, cache reads, one token at a time)
# and XLA's CPU matmul precision, which leave logits (of order 1 here)
# within ~1e-5 of each other. 1e-3 keeps a hundredfold margin on that,
# while computing in bfloat16 (8-bit mantissa, ~4e-3 relative per
# operation) moves logits by ~1e-2 and fails it.
LOGIT_TOL = 1e-3


def model(dtype):
    return Model(ModelConfig(
        name="tiny-qwen3", family="dense", source="test",
        num_layers=CFG["num_hidden_layers"], d_model=CFG["hidden_size"],
        num_heads=CFG["num_attention_heads"],
        num_kv_heads=CFG["num_key_value_heads"], head_dim=CFG["head_dim"],
        d_ff=CFG["intermediate_size"], vocab_size=CFG["vocab_size"],
        qk_norm=True, rope_theta=float(CFG["rope_theta"]), dtype=dtype,
        param_dtype="float32", norm_eps=CFG["rms_norm_eps"]), remat=False)


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(0).randint(0, CFG["vocab_size"], (2, 24))


def test_reference_weights_equal_the_models_from_the_seed():
    ours = R.init_params(CFG, SEED)
    theirs = model("float32").init(jax.random.PRNGKey(SEED & 0xFFFFFFFF))
    flat_o = dict(jax.tree_util.tree_flatten_with_path(ours)[0])
    flat_t = dict(jax.tree_util.tree_flatten_with_path(theirs)[0])
    assert flat_o.keys() == flat_t.keys()
    for k in flat_o:
        np.testing.assert_array_equal(np.asarray(flat_o[k]),
                                      np.asarray(flat_t[k]), err_msg=str(k))


def prefill_then_decode(m, params, tokens, prompt):
    """Logits at positions prompt-1 .. S-2: prefill the prompt, then feed
    the remaining tokens one at a time through the cache."""
    B, S = tokens.shape
    cache = m.init_cache(B, S)
    lg, cache = jax.jit(m.prefill)(params, jnp.asarray(tokens[:, :prompt]),
                                   cache)
    out = [np.asarray(lg)]
    decode = jax.jit(m.decode_step)
    for t in range(prompt, S - 1):
        lg, cache = decode(params, jnp.asarray(tokens[:, t:t + 1]), cache,
                           jnp.full((B,), t, jnp.int32))
        out.append(np.asarray(lg))
    return np.stack(out, 1)


def test_model_prefill_and_cached_decode_match_reference(tokens):
    m = model("float32")
    params = R.init_params(CFG, SEED)
    prompt = 10
    got = prefill_then_decode(m, params, tokens, prompt)
    ref = np.asarray(R.logits(CFG, params, jnp.asarray(tokens)))
    ref = ref[:, prompt - 1:tokens.shape[1] - 1]
    assert np.max(np.abs(got - ref)) < LOGIT_TOL


def test_bfloat16_reference_fails_the_tolerance(tokens):
    params = R.init_params(CFG, SEED)
    ref = np.asarray(R.logits(CFG, params, jnp.asarray(tokens)))
    bf16 = jax.tree.map(lambda w: w.astype(jnp.bfloat16), params)
    low = np.asarray(R.logits(CFG, bf16, jnp.asarray(tokens)),
                     np.float32)
    assert np.max(np.abs(low - ref)) > LOGIT_TOL


def test_float8_control_moves_served_logprobs(tokens):
    params = R.init_params(CFG, SEED)
    t = jnp.asarray(tokens)
    ref = np.asarray(R.token_logprobs(CFG, params, t))
    low = np.asarray(R.token_logprobs(CFG, params, t, lowp=True))
    assert np.max(np.abs(low - ref)) > 10 * LOGIT_TOL


def test_unknown_model_type_has_no_reference():
    with pytest.raises(ValueError, match="no reference model"):
        R.family("no_such_family")
