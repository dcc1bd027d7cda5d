"""Parity between the Pallas kernels and the MODEL's jnp implementations
(the kernels must be drop-in replacements for the layers they accelerate,
not just match the standalone oracles)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import rwkv6 as R

# kernel JIT dominates tier-1 wall time; the fast CI job skips these
pytestmark = pytest.mark.slow

KEY = jax.random.PRNGKey(7)


def k(i):
    return jax.random.fold_in(KEY, i)


def test_flash_matches_model_attention():
    cfg = get_config("llama3.2-3b").reduced()
    B, S, H, kvH, hd = 2, 128, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jax.random.normal(k(0), (B, H, S, hd))
    kk = jax.random.normal(k(1), (B, kvH, S, hd))
    v = jax.random.normal(k(2), (B, kvH, S, hd))
    model_out = L._attend_causal(q, kk, v, cfg, window=None, q_chunk=64)
    kern_out = flash_attention(q, kk, v, block_q=64, block_k=64,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(kern_out), np.asarray(model_out),
                               atol=2e-5, rtol=2e-5)


def test_decode_matches_model_attention_decode():
    """Kernel vs the model's cache-attention math for one decode step, with
    a dense cache of valid length L (new token already written)."""
    cfg = get_config("llama3.2-3b").reduced()
    B, S, kvH, hd = 2, 256, cfg.num_kv_heads, cfg.head_dim
    H = cfg.num_heads
    length = 100
    q = jax.random.normal(k(3), (B, H, hd))
    kc = jax.random.normal(k(4), (B, kvH, S, hd))
    vc = jax.random.normal(k(5), (B, kvH, S, hd))
    lengths = jnp.full((B,), length)
    out_k = decode_attention(q, kc, vc, lengths, block_k=64,
                             interpret=True)
    # model-side reference: grouped scores + masked softmax (the math inside
    # L.attention_decode after the cache write)
    scores = L._grouped_scores(q[:, :, None, :], kc, cfg)
    valid = jnp.arange(S)[None, :] < lengths[:, None]
    scores = jnp.where(valid[:, None, None, None, :], scores, L.NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out_m = jnp.einsum("bkgst,bkth->bkgsh", probs, vc).reshape(B, H, hd)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_m),
                               atol=2e-5, rtol=2e-5)


def test_rwkv6_kernel_matches_model_chunked_wkv():
    cfg = get_config("rwkv6-7b").reduced()
    H, hd = cfg.num_rwkv_heads, cfg.rwkv_head_dim
    B, S = 2, 96
    r = jax.random.normal(k(6), (B, S, H, hd))
    kk = jax.random.normal(k(7), (B, S, H, hd))
    v = jax.random.normal(k(8), (B, S, H, hd))
    lw = jnp.clip(-jnp.exp(jax.random.normal(k(9), (B, S, H, hd))),
                  R.LW_MIN, R.LW_MAX)
    u = jax.random.normal(k(10), (H, hd)) * 0.3
    S0 = jnp.zeros((B, H, hd, hd))
    y_model, S_model = R._chunked_wkv(r, kk, v, lw, u, S0)
    y_kern, S_kern = rwkv6_scan(r, kk, v, lw, u, chunk=R.RWKV_CHUNK,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(y_kern), np.asarray(y_model),
                               atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(S_kern), np.asarray(S_model),
                               atol=5e-4, rtol=5e-4)


def test_mamba_kernel_matches_model_scan():
    """The kernel consumes the same (delta, B, C) the model computes."""
    cfg = get_config("jamba-v0.1-52b").reduced()
    B, S = 1, 64
    di, ds = cfg.mamba_d_inner, cfg.mamba_d_state
    p = M.init_mamba(k(11), cfg)
    xc = jax.nn.silu(jax.random.normal(k(12), (B, S, di)))
    a, b_, Cm = M._ssm_inputs(p, cfg, xc)
    # model path: associative scan of (a, b)
    _, h = jax.lax.associative_scan(M._scan_combine, (a, b_), axis=1)
    y_model = jnp.sum(h * Cm[:, :, None, :], axis=-1) \
        + p["D"].astype(jnp.float32) * xc.astype(jnp.float32)
    # kernel path: recompute delta the same way the model does
    dr = M.dt_rank(cfg)
    xdbl = jnp.einsum("bsi,ir->bsr", xc, p["x_proj"].astype(xc.dtype))
    dt_r, Bm, Cm2 = jnp.split(xdbl, [dr, dr + ds], axis=-1)
    delta = jax.nn.softplus(
        jnp.einsum("bsr,ri->bsi", dt_r, p["dt_proj"].astype(xc.dtype))
        .astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    y_kern, h_kern = mamba_scan(xc.astype(jnp.float32), delta,
                                Bm.astype(jnp.float32),
                                Cm2.astype(jnp.float32),
                                p["A_log"], p["D"].astype(jnp.float32),
                                chunk=32, block_d=64, interpret=True)
    np.testing.assert_allclose(np.asarray(y_kern), np.asarray(y_model),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(h_kern), np.asarray(h[:, -1]),
                               atol=2e-4, rtol=2e-4)
