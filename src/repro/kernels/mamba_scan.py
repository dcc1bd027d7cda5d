"""Pallas TPU Mamba selective scan.

The hardware-aware scan: per (batch, d_inner-block), chunks of the sequence
stream through VMEM while the [bd, ds] state stays resident in fp32
scratch; within a chunk the recurrence h_t = a_t*h_{t-1} + b_t runs as an
in-register fori_loop (ds and the chunk fit VMEM, so nothing [S, di, ds]
ever touches HBM — the memory property the jnp path approximates with
chunked associative scans).

Grid: (batch * d_inner_blocks, num_chunks), chunks innermost/sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_log_ref, d_ref, y_ref, h_out_ref,
            h_ref, *, chunk: int, block_d: int, ds: int):
    ic = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)                 # [C, bd]
    dt = dt_ref[0].astype(jnp.float32)               # [C, bd]
    Bm = b_ref[0].astype(jnp.float32)                # [C, ds]
    Cm = c_ref[0].astype(jnp.float32)                # [C, ds]
    A = -jnp.exp(a_log_ref[...].astype(jnp.float32))  # [bd, ds]
    D = d_ref[0].astype(jnp.float32)                 # [bd]

    def body(t, carry):
        h, y = carry                                 # h: [bd, ds]
        a_t = jnp.exp(dt[t][:, None] * A)            # [bd, ds]
        b_t = (dt[t] * x[t])[:, None] * Bm[t][None, :]
        h = a_t * h + b_t
        y_t = jnp.sum(h * Cm[t][None, :], axis=1) + D * x[t]
        y = jax.lax.dynamic_update_slice(y, y_t[None, :], (t, 0))
        return h, y

    h0 = h_ref[...]
    y0 = jnp.zeros((chunk, block_d), jnp.float32)
    h_fin, y = jax.lax.fori_loop(0, chunk, body, (h0, y0))
    h_ref[...] = h_fin
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _emit():
        h_out_ref[0] = h_ref[...]


def mamba_scan(x, delta, Bm, Cm, A_log, D, *, chunk: int = 64,
               block_d: int = 128, interpret: bool):
    """x/delta: [B,S,di]; Bm/Cm: [B,S,ds]; A_log: [di,ds]; D: [di].

    Returns (y [B,S,di] fp32, h_out [B,di,ds] fp32)."""
    B, S, di = x.shape
    ds = A_log.shape[1]
    chunk = min(chunk, S)
    block_d = min(block_d, di)
    assert S % chunk == 0 and di % block_d == 0
    nd = di // block_d
    nc = S // chunk

    def xd_map(bd, ic):
        return (bd // nd, ic, bd % nd)

    def bc_map(bd, ic):
        return (bd // nd, ic, 0)

    def a_map(bd, ic):
        return (bd % nd, 0)

    def d_map(bd, ic):
        return (0, bd % nd)

    y, h_out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, block_d=block_d, ds=ds),
        grid=(B * nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), xd_map),
            pl.BlockSpec((1, chunk, block_d), xd_map),
            pl.BlockSpec((1, chunk, ds), bc_map),
            pl.BlockSpec((1, chunk, ds), bc_map),
            pl.BlockSpec((block_d, ds), a_map),
            pl.BlockSpec((1, block_d), d_map),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), xd_map),
            pl.BlockSpec((1, block_d, ds),
                         lambda bd, ic: (bd // nd, bd % nd, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, di), jnp.float32),
            jax.ShapeDtypeStruct((B, di, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, ds), jnp.float32)],
        interpret=interpret,
    )(x, delta, Bm, Cm, A_log, D.reshape(1, di))
    return y, h_out
