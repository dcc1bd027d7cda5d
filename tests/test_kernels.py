"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests,
all asserting allclose against the pure-jnp oracles in repro.kernels.ref,
with kernels executed in interpret mode (CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # CI image without hypothesis: run the property
    from _hyp_compat import given, settings, st   # tests on deterministic
    # fallback examples instead of skipping the whole module

from repro.kernels import ref as R
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.rwkv6_scan import rwkv6_scan

# kernel JIT dominates tier-1 wall time; the fast CI job skips these
pytestmark = pytest.mark.slow

KEY = jax.random.PRNGKey(0)


def k(i):
    return jax.random.fold_in(KEY, i)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,H,kvH,S,hd", [
    (1, 2, 2, 64, 32),      # MHA
    (2, 4, 2, 128, 64),     # GQA 2:1
    (1, 8, 2, 256, 32),     # GQA 4:1
    (2, 6, 1, 64, 128),     # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, H, kvH, S, hd, dtype):
    q = jax.random.normal(k(1), (B, H, S, hd), dtype)
    kk = jax.random.normal(k(2), (B, kvH, S, hd), dtype)
    v = jax.random.normal(k(3), (B, kvH, S, hd), dtype)
    o = flash_attention(q, kk, v, block_q=64, block_k=64, interpret=True)
    r = R.flash_ref(q, kk, v)
    tol = 5e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("block_q,block_k", [(32, 32), (64, 128), (128, 64)])
def test_flash_attention_blocks(block_q, block_k):
    B, H, kvH, S, hd = 1, 4, 4, 128, 64
    q = jax.random.normal(k(4), (B, H, S, hd))
    kk = jax.random.normal(k(5), (B, kvH, S, hd))
    v = jax.random.normal(k(6), (B, kvH, S, hd))
    o = flash_attention(q, kk, v, block_q=block_q, block_k=block_k,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(R.flash_ref(q, kk, v)),
                               atol=5e-6, rtol=5e-6)


def test_flash_non_causal():
    B, H, kvH, S, hd = 1, 2, 2, 64, 32
    q = jax.random.normal(k(7), (B, H, S, hd))
    kk = jax.random.normal(k(8), (B, kvH, S, hd))
    v = jax.random.normal(k(9), (B, kvH, S, hd))
    o = flash_attention(q, kk, v, causal=False, block_q=32, block_k=32,
                        interpret=True)
    r = R.flash_ref(q, kk, v, causal=False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=5e-6,
                               rtol=5e-6)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,H,kvH,S,hd,bk", [
    (2, 4, 2, 512, 64, 128),
    (3, 8, 2, 256, 32, 64),
    (1, 2, 2, 128, 128, 128),
])
def test_decode_attention(B, H, kvH, S, hd, bk):
    q = jax.random.normal(k(10), (B, H, hd))
    kc = jax.random.normal(k(11), (B, kvH, S, hd))
    vc = jax.random.normal(k(12), (B, kvH, S, hd))
    lengths = jnp.asarray([(S // 2 + 7 * i) % S + 1 for i in range(B)])
    o = decode_attention(q, kc, vc, lengths, block_k=bk, interpret=True)
    r = R.decode_ref(q, kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=5e-6,
                               rtol=5e-6)


@given(length_frac=st.floats(0.05, 1.0), seed=st.integers(0, 2 ** 16))
@settings(max_examples=10, deadline=None)
def test_decode_attention_lengths_property(length_frac, seed):
    """Property: masked cache positions never influence the output."""
    B, H, kvH, S, hd = 1, 2, 2, 128, 32
    kp = jax.random.PRNGKey(seed)
    q = jax.random.normal(jax.random.fold_in(kp, 0), (B, H, hd))
    kc = jax.random.normal(jax.random.fold_in(kp, 1), (B, kvH, S, hd))
    vc = jax.random.normal(jax.random.fold_in(kp, 2), (B, kvH, S, hd))
    length = max(1, int(S * length_frac))
    lengths = jnp.asarray([length])
    o1 = decode_attention(q, kc, vc, lengths, block_k=32, interpret=True)
    # poison the masked region: output must not change
    poison = kc.at[:, :, length:, :].set(1e6)
    poison_v = vc.at[:, :, length:, :].set(-1e6)
    o2 = decode_attention(q, poison, poison_v, lengths, block_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


# ---------------------------------------------------------------------------
# rwkv6
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,hd,chunk", [
    (1, 32, 2, 32, 32),
    (2, 96, 3, 32, 32),
    (1, 64, 1, 64, 16),
])
def test_rwkv6_scan(B, S, H, hd, chunk):
    r = jax.random.normal(k(20), (B, S, H, hd))
    kk = jax.random.normal(k(21), (B, S, H, hd))
    v = jax.random.normal(k(22), (B, S, H, hd))
    lw = jnp.clip(-jnp.exp(jax.random.normal(k(23), (B, S, H, hd))),
                  -2.5, -1e-4)
    u = jax.random.normal(k(24), (H, hd)) * 0.5
    y, S_out = rwkv6_scan(r, kk, v, lw, u, chunk=chunk, interpret=True)
    yr, S_ref = R.rwkv6_ref(r, kk, v, lw, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=3e-4,
                               rtol=3e-4)
    np.testing.assert_allclose(np.asarray(S_out), np.asarray(S_ref),
                               atol=3e-4, rtol=3e-4)


@given(seed=st.integers(0, 2 ** 16), chunk=st.sampled_from([8, 16, 32]))
@settings(max_examples=8, deadline=None)
def test_rwkv6_chunk_invariance(seed, chunk):
    """Property: any chunk size within the stability bound (chunk*2.5 < 85)
    matches the sequential oracle. chunk=64 violates the bound and is
    rejected by the kernel's assertion (tested below)."""
    B, S, H, hd = 1, 64, 2, 16
    kp = jax.random.PRNGKey(seed)
    r = jax.random.normal(jax.random.fold_in(kp, 0), (B, S, H, hd))
    kk = jax.random.normal(jax.random.fold_in(kp, 1), (B, S, H, hd))
    v = jax.random.normal(jax.random.fold_in(kp, 2), (B, S, H, hd))
    lw = jnp.clip(-jnp.exp(jax.random.normal(jax.random.fold_in(kp, 3),
                                             (B, S, H, hd))), -2.5, -1e-4)
    u = jnp.zeros((H, hd))
    y1, s1 = rwkv6_scan(r, kk, v, lw, u, chunk=chunk, interpret=True)
    y2, s2 = R.rwkv6_ref(r, kk, v, lw, u)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=5e-4,
                               rtol=5e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=5e-4,
                               rtol=5e-4)


def test_rwkv6_rejects_unstable_chunk():
    B, S, H, hd = 1, 64, 1, 16
    z = jnp.zeros((B, S, H, hd))
    with pytest.raises(AssertionError):
        rwkv6_scan(z, z, z, z - 1.0, jnp.zeros((H, hd)), chunk=64,
                   interpret=True)


# ---------------------------------------------------------------------------
# mamba
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,di,ds,chunk,bd", [
    (1, 64, 128, 16, 32, 128),
    (2, 128, 256, 16, 32, 64),
    (1, 32, 64, 8, 16, 32),
])
def test_mamba_scan(B, S, di, ds, chunk, bd):
    x = jax.random.normal(k(30), (B, S, di))
    delta = jax.nn.softplus(jax.random.normal(k(31), (B, S, di)) - 2)
    Bm = jax.random.normal(k(32), (B, S, ds))
    Cm = jax.random.normal(k(33), (B, S, ds))
    A_log = jax.random.normal(k(34), (di, ds)) * 0.5
    D = jax.random.normal(k(35), (di,))
    y, h = mamba_scan(x, delta, Bm, Cm, A_log, D, chunk=chunk, block_d=bd,
                      interpret=True)
    yr, hr = R.mamba_ref(x, delta, Bm, Cm, A_log, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-5,
                               rtol=1e-5)


@given(seed=st.integers(0, 2 ** 16))
@settings(max_examples=6, deadline=None)
def test_mamba_state_continuation_property(seed):
    """Property: scanning [0:S] equals scanning [0:S/2] then [S/2:S] with the
    carried state (verified via the oracle's h0 support)."""
    B, S, di, ds = 1, 64, 32, 8
    kp = jax.random.PRNGKey(seed)
    x = jax.random.normal(jax.random.fold_in(kp, 0), (B, S, di))
    delta = jax.nn.softplus(
        jax.random.normal(jax.random.fold_in(kp, 1), (B, S, di)) - 2)
    Bm = jax.random.normal(jax.random.fold_in(kp, 2), (B, S, ds))
    Cm = jax.random.normal(jax.random.fold_in(kp, 3), (B, S, ds))
    A_log = jax.random.normal(jax.random.fold_in(kp, 4), (di, ds)) * 0.3
    D = jnp.zeros((di,))
    y_full, h_full = mamba_scan(x, delta, Bm, Cm, A_log, D, chunk=16,
                                block_d=32, interpret=True)
    half = S // 2
    _, h1 = mamba_scan(x[:, :half], delta[:, :half], Bm[:, :half],
                       Cm[:, :half], A_log, D, chunk=16, block_d=32,
                       interpret=True)
    y2, h2 = R.mamba_ref(x[:, half:], delta[:, half:], Bm[:, half:],
                         Cm[:, half:], A_log, D, h0=h1)
    np.testing.assert_allclose(np.asarray(y_full[:, half:]), np.asarray(y2),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_full), np.asarray(h2), atol=1e-4,
                               rtol=1e-4)
