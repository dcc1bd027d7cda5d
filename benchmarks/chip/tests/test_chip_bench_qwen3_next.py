"""The system's Qwen3-Next layers against the benchmark's float32 reference
(``reference_models/qwen3_next.py``), and against themselves, at a tiny
Qwen3-Next shape on the CPU: hidden 64; linear attention 4 key / 8 value
heads of 16; gated attention 4 / 2 heads of 32 with a quarter of each head
rotated; 16 router experts, top 4, 4 held (router ids 4-7) and a gated
shared expert; float32."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import reference as R
from benchmarks.chip.reference_models import qwen3_next as RQ
from repro.configs.base import ModelConfig
from repro.models import Model
from repro.models import gdn as G
from repro.models import moe as MOE
from repro.rl import losses as LO

CFG = {"model_type": "qwen3_next", "num_hidden_layers": 4, "hidden_size": 64,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
       "partial_rotary_factor": 0.25, "rope_theta": 10000000,
       "rms_norm_eps": 1e-6, "vocab_size": 128, "full_attention_interval": 4,
       "linear_num_key_heads": 4, "linear_num_value_heads": 8,
       "linear_key_head_dim": 16, "linear_value_head_dim": 16,
       "linear_conv_kernel_dim": 4, "num_experts": 4,
       "num_experts_per_tok": 4, "moe_intermediate_size": 16,
       "shared_expert_intermediate_size": 16,
       "deployment": {"router_experts": 16, "expert_offset": 4}}
SEED = 2 ** 31 + 11

# Prefill + cached decode (or the train step's forward) in float32 against
# the reference's teacher-forced forward. Both compute in float32; what
# differs is the order of the sums: the chunked (WY) delta rule with its
# triangular solve against the reference's token-by-token recurrence,
# blocked attention, cache reads, and XLA's CPU matmul precision. That
# leaves logits (of order 1-4 here) within 2.5e-4 of each other. 1e-3
# keeps four times that, while computing in bfloat16 moves logits by more
# than 1 at this shape (the routing's top-k flips on rounding) and fails.
LOGIT_TOL = 1e-3
# Gradients of the GRPO loss, per leaf, as a share of the reference
# leaf's largest element: float32 on both sides reads under 1e-4 here;
# bfloat16 compute reads above 0.05.
GRAD_TOL = 2e-3


def config(dtype="float32", held=4, offset=4, shared=1, router=16):
    return ModelConfig(
        name="tiny-qwen3-next", family="hybrid", source="test",
        num_layers=CFG["num_hidden_layers"], d_model=CFG["hidden_size"],
        num_heads=CFG["num_attention_heads"],
        num_kv_heads=CFG["num_key_value_heads"], head_dim=CFG["head_dim"],
        d_ff=64, vocab_size=CFG["vocab_size"],
        block_pattern=(("gdn", "moe"),) * 3 + (("attn", "moe"),),
        qk_norm=True, attn_output_gate=True, partial_rotary_factor=0.25,
        zero_centered_norm=True, rope_theta=float(CFG["rope_theta"]),
        linear_num_key_heads=4, linear_num_value_heads=8,
        linear_key_head_dim=16, linear_value_head_dim=16,
        linear_conv_kernel_dim=4, num_experts=held, router_experts=router,
        expert_offset=offset, top_k=4, moe_d_ff=16,
        num_shared_experts=shared, shared_expert_gate=bool(shared),
        router_aux_coef=0.0, router_z_coef=0.0, dtype=dtype,
        param_dtype="float32", norm_eps=CFG["rms_norm_eps"])


def model(dtype="float32"):
    return Model(config(dtype), remat=False)


@pytest.fixture(scope="module")
def params():
    return RQ.init_params(CFG, SEED)


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(0).randint(0, CFG["vocab_size"], (2, 90))


@pytest.fixture(scope="module")
def ref_logits(params, tokens):
    return np.asarray(R.logits(CFG, params, jnp.asarray(tokens)))


def test_reference_weights_equal_the_models_from_the_seed(params):
    theirs = model().init(jax.random.PRNGKey(SEED & 0xFFFFFFFF))
    flat_o = dict(jax.tree_util.tree_flatten_with_path(params)[0])
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
    out = [np.asarray(lg, np.float32)]
    decode = jax.jit(m.decode_step)
    for t in range(prompt, S - 1):
        lg, cache = decode(params, jnp.asarray(tokens[:, t:t + 1]), cache,
                           jnp.full((B,), t, jnp.int32))
        out.append(np.asarray(lg, np.float32))
    return np.stack(out, 1)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_and_cached_decode_against_reference(params, tokens,
                                                     ref_logits, dtype):
    """float32 holds the tolerance; bfloat16 compute fails it."""
    prompt = 70      # past one 64-token chunk of the delta rule
    got = prefill_then_decode(model(dtype), params, tokens, prompt)
    gap = np.max(np.abs(got - ref_logits[:, prompt - 1:-1]))
    assert (gap < LOGIT_TOL) == (dtype == "float32"), gap


def grads(fn, params):
    return dict(jax.tree_util.tree_flatten_with_path(jax.grad(fn)(params))[0])


@pytest.fixture(scope="module")
def grpo_batch(tokens):
    rng = np.random.RandomState(1)
    B, S = tokens.shape
    return {"tokens": jnp.asarray(tokens),
            "loss_mask": jnp.asarray(
                (np.arange(S)[None] >= 20).repeat(B, 0), jnp.float32),
            "advantages": jnp.asarray([1.0, -0.5]),
            "behavior_logprobs": jnp.asarray(
                rng.uniform(-6, -4, (B, S - 1)), jnp.float32)}


@pytest.fixture(scope="module")
def ref_grads(params, grpo_batch):
    with jax.default_matmul_precision("highest"):
        return grads(lambda p: R.grpo_loss(CFG, p, grpo_batch), params)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_grpo_gradients_against_reference(params, grpo_batch, ref_grads,
                                          dtype):
    """The train step's GRPO loss (the model's fused logprob head, as
    ``trainer.make_grpo_train_step`` computes it) differentiated against
    the reference's loss, leaf by leaf."""
    m, b = model(dtype), grpo_batch

    def ours(p):
        lp, _ = m.forward_logprobs(p, b["tokens"])
        return LO.grpo_from_logprobs(
            lp, b["tokens"], b["loss_mask"], b["advantages"],
            b["behavior_logprobs"])[0]

    got = grads(ours, params)
    assert got.keys() == ref_grads.keys()
    worst = max(float(np.max(np.abs(np.asarray(got[k], np.float32)
                                    - np.asarray(ref_grads[k])))
                      / max(float(np.max(np.abs(np.asarray(ref_grads[k])))),
                            1e-12))
                for k in ref_grads)
    assert (worst < GRAD_TOL) == (dtype == "float32"), worst


def test_padded_prefill_equals_unpadded(params, tokens):
    """A prompt padded to a bucket, with ``last_pos`` at its real end,
    gives the unpadded prompt's logits and DeltaNet S and conv state."""
    m = model()
    n, bucket = 37, 64
    toks = jnp.asarray(tokens[:, :n])
    padded = jnp.concatenate(
        [toks, jnp.full((2, bucket - n), 7, toks.dtype)], axis=1)
    lg, cache = m.prefill(params, toks, m.init_cache(2, 96))
    lg_p, cache_p = m.prefill(params, padded, m.init_cache(2, 96),
                              last_pos=jnp.full((2,), n - 1, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg), atol=1e-5)
    for j in range(3):                       # the DeltaNet layers
        for leaf in ("S", "conv"):
            np.testing.assert_allclose(np.asarray(cache_p[j][leaf]),
                                       np.asarray(cache[j][leaf]),
                                       atol=1e-5, err_msg=f"{j} {leaf}")


def test_chunked_delta_rule_equals_per_token_step():
    """The chunked form over a prompt, then over a continuation from the
    prompt's state, equals the per-token step over both."""
    r = np.random.RandomState(2)
    B, S, H, dk, dv = 2, 150, 3, 8, 6
    q = r.randn(B, S, H, dk).astype(np.float32) / 3
    k = r.randn(B, S, H, dk).astype(np.float32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.randn(B, S, H, dv).astype(np.float32)
    g = -r.uniform(0, 0.5, (B, S, H)).astype(np.float32)
    beta = r.uniform(0, 1, (B, S, H)).astype(np.float32)
    state = np.zeros((B, H, dk, dv), np.float32)
    outs = []
    for t in range(S):
        o, state = G.delta_rule_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                     beta[:, t], state)
        outs.append(o)
    want = np.stack(outs, 1)
    cut = 100                                # a prompt of 1.6 chunks
    s0 = jnp.zeros((B, H, dk, dv))
    o1, s1 = G.chunk_delta_rule(q[:, :cut], k[:, :cut], v[:, :cut],
                                g[:, :cut], beta[:, :cut], s0, jnp.float32)
    o2, s2 = G.chunk_delta_rule(q[:, cut:], k[:, cut:], v[:, cut:],
                                g[:, cut:], beta[:, cut:], s1, jnp.float32)
    np.testing.assert_allclose(np.concatenate([o1, o2], 1), want,
                               atol=2e-5)
    np.testing.assert_allclose(s2, state, atol=2e-5)


def test_chip_shares_sum_to_the_uncut_layer(params):
    """Four disjoint 4-of-16 expert shares of one MoE layer, each routing
    over all 16 experts, add up (the shared expert counted once) to the
    reference's layer holding all 16."""
    r = np.random.RandomState(3)
    d, f, E = 64, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    whole = {"router": jax.random.normal(ks[0], (d, E)) * 0.3,
             "w_gate": jax.random.normal(ks[1], (E, d, f)) / 8,
             "w_up": jax.random.normal(ks[2], (E, d, f)) / 8,
             "w_down": jax.random.normal(ks[3], (E, f, d)) / 4}
    shared = jax.tree.map(lambda w: w[0],
                          {k: params["layers"][0]["moe"][k] for k in
                           ("shared_gate", "shared_up", "shared_down",
                            "shared_expert_gate")})
    x = jnp.asarray(r.randn(2, 12, d), jnp.float32)
    total = 0.0
    for i, first in enumerate(range(0, E, 4)):
        part = {k: whole[k] if k == "router" else whole[k][first:first + 4]
                for k in whole}
        if i == 0:
            part.update(shared)
        cfg = config(held=4, offset=first, shared=int(i == 0), router=E)
        y, _ = MOE.moe_fwd(part, cfg, x)
        total = total + np.asarray(y)
    uncut = dict(CFG, num_experts=E,
                 deployment={"router_experts": E, "expert_offset": 0})
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda row: RQ._moe(uncut, dict(whole, **shared),
                                            row, False))(x)
    np.testing.assert_allclose(total, np.asarray(want), atol=1e-5)


def test_moe_counts_what_lands_here(params):
    """The layer's counters: assignments to held experts, the busiest
    held expert, held experts touched; tokens that are not live route
    nowhere."""
    cfg = config()
    p = jax.tree.map(lambda w: w[0], params["layers"][0]["moe"])
    x = jnp.asarray(np.random.RandomState(4).randn(1, 40, 64), jnp.float32)
    _, aux = MOE.moe_fwd(p, cfg, x)
    idx = np.asarray(jax.lax.top_k(jax.nn.softmax(
        x[0] @ p["router"], -1), 4)[1])
    here = (idx >= 4) & (idx < 8)
    counts = np.bincount(idx[here] - 4, minlength=4)
    assert int(aux["tokens_here"]) == here.sum()
    assert int(aux["load_max"]) == counts.max()
    assert int(aux["touched"]) == (counts > 0).sum()
    live = jnp.zeros((1, 40), bool).at[0, :5].set(True)
    _, aux = MOE.moe_fwd(p, cfg, x, live=live)
    assert int(aux["tokens_here"]) == here[:5].sum()


def test_rows_past_the_last_group_stay_out(monkeypatch, params):
    """On the TPU the grouped matmul leaves the rows past its last group
    undefined (on the CPU they are zero). With those rows set to NaN, the
    layer's output and its gradients are as before."""
    cfg = config()
    p = jax.tree.map(lambda w: w[0], params["layers"][0]["moe"])
    x = jnp.asarray(np.random.RandomState(5).randn(1, 24, 64), jnp.float32)

    def loss(p):
        return jnp.sum(MOE.moe_fwd(p, cfg, x)[0] ** 2)
    want, want_g = jax.value_and_grad(loss)(p)
    real = jax.lax.ragged_dot

    def undefined_past_groups(lhs, rhs, group_sizes, **kw):
        out = real(lhs, rhs, group_sizes, **kw)
        rows = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(rows[:, None], out, jnp.nan)
    monkeypatch.setattr(jax.lax, "ragged_dot", undefined_past_groups)
    got, got_g = jax.value_and_grad(loss)(p)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for k in want_g:
        np.testing.assert_allclose(np.asarray(got_g[k]),
                                   np.asarray(want_g[k]), atol=1e-6,
                                   err_msg=k)
