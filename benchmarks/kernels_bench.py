"""Per-kernel microbenchmark: us_per_call of the Pallas kernels (interpret
mode on CPU — correctness-path timing, NOT TPU perf) vs the jnp oracle."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Bench, fmt
from repro.kernels import ref as R
from repro.kernels.decode_attention import decode_attention, \
    ragged_paged_decode
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.rwkv6_scan import rwkv6_scan


def timeit(fn, *args, n=3):
    fn(*args)
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6


def run():
    b = Bench("kernels")
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 4, 128, 64))
    k = jax.random.normal(key, (1, 2, 128, 64))
    v = jax.random.normal(key, (1, 2, 128, 64))
    b.row("flash_attention_us", fmt(timeit(
        lambda *a: flash_attention(*a, block_q=64, block_k=64,
                                   interpret=True), q, k, v), 0))
    b.row("flash_ref_us", fmt(timeit(R.flash_ref, q, k, v), 0))

    qd = jax.random.normal(key, (2, 4, 64))
    kc = jax.random.normal(key, (2, 2, 256, 64))
    lens = jnp.asarray([128, 256])
    b.row("decode_attention_us", fmt(timeit(
        lambda *a: decode_attention(*a, block_k=128, interpret=True),
        qd, kc, kc, lens), 0))
    b.row("decode_ref_us", fmt(timeit(R.decode_ref, qd, kc, kc, lens), 0))

    # ragged paged decode at 1-of-4 occupancy: one 64-token row, three
    # inactive. The page-table walk skips pages at/after each row's
    # length, so KV bytes streamed scale with ceil(len/page) pages per
    # row; the dense kernel streams the whole B x S cache slab. The
    # bytes-touched roofline rows quantify that gap (the us timings here
    # are interpret-mode correctness-path numbers, not TPU perf).
    page, P, B4, kvH, hd = 16, 16, 4, 2, 64
    q4 = jax.random.normal(key, (B4, 4, hd))
    pool_k = jax.random.normal(key, (B4 * P + 1, kvH, page, hd))
    pool_v = jax.random.normal(key, (B4 * P + 1, kvH, page, hd))
    tables = jnp.arange(B4 * P, dtype=jnp.int32).reshape(B4, P)
    lens4 = jnp.asarray([64, 0, 0, 0], jnp.int32)
    b.row("ragged_paged_decode_us", fmt(timeit(
        lambda *a: ragged_paged_decode(*a, interpret=True),
        q4, pool_k, pool_v, tables, lens4), 0))
    gk = jnp.moveaxis(pool_k[tables], 2, 1).reshape(B4, kvH, P * page, hd)
    gv = jnp.moveaxis(pool_v[tables], 2, 1).reshape(B4, kvH, P * page, hd)
    b.row("ragged_gathered_ref_us", fmt(timeit(
        R.decode_ref, q4, gk, gv, lens4), 0))
    np.testing.assert_allclose(
        np.asarray(ragged_paged_decode(q4, pool_k, pool_v, tables, lens4,
                                       interpret=True))[0],
        np.asarray(R.decode_ref(q4, gk, gv, lens4))[0],
        rtol=2e-5, atol=2e-5)
    kv_elt = 2 * kvH * page * hd * 4          # k+v page pair, fp32 bytes
    dense_bytes = B4 * P * kv_elt             # full slab, every call
    ragged_bytes = int(sum(-(-int(n) // page) for n in lens4)) * kv_elt
    b.row("roofline_decode_kv_bytes_dense", dense_bytes)
    b.row("roofline_decode_kv_bytes_ragged", ragged_bytes)
    b.row("roofline_decode_kv_bytes_frac",
          fmt(ragged_bytes / dense_bytes, 3), "<1.0")

    r = jax.random.normal(key, (1, 64, 2, 32))
    lw = jnp.clip(-jnp.exp(jax.random.normal(key, (1, 64, 2, 32))),
                  -2.5, -1e-4)
    u = jnp.zeros((2, 32))
    b.row("rwkv6_scan_us", fmt(timeit(
        lambda *a: rwkv6_scan(*a, chunk=32, interpret=True)[0],
        r, r, r, lw, u), 0))
    b.row("rwkv6_ref_us", fmt(timeit(
        lambda *a: R.rwkv6_ref(*a)[0], r, r, r, lw, u), 0))

    x = jax.random.normal(key, (1, 64, 128))
    dt = jax.nn.softplus(jax.random.normal(key, (1, 64, 128)) - 2)
    Bm = jax.random.normal(key, (1, 64, 16))
    A_log = jnp.zeros((128, 16))
    D = jnp.ones((128,))
    b.row("mamba_scan_us", fmt(timeit(
        lambda *a: mamba_scan(*a, chunk=32, block_d=128,
                              interpret=True)[0],
        x, dt, Bm, Bm, A_log, D), 0))
    b.row("mamba_ref_us", fmt(timeit(
        lambda *a: R.mamba_ref(*a)[0], x, dt, Bm, Bm, A_log, D), 0))
    b.save()
    return b


if __name__ == "__main__":
    run()
