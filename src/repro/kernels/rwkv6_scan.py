"""Pallas TPU RWKV6 chunked WKV scan.

Implements the same chunked linear-attention formulation as the jnp model
path (models/rwkv6.py): per chunk, intra-chunk contributions are two
[C,C]x[C,hd] matmuls (MXU-friendly) plus the u-bonus diagonal; the cross-
chunk state S in R^{hd x hd} lives in fp32 VMEM scratch and is carried
sequentially across the chunk grid dimension. Decay stability relies on the
model's log-decay clamp (|lw| <= 2.5 per token, chunk <= 32 -> exponents
< 88, see models/rwkv6.py).

Grid: (batch*heads, num_chunks), chunk dim innermost/sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, s_out_ref, S_ref, *,
            chunk: int):
    ic = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ic == 0)
    def _init():
        S_ref[...] = jnp.zeros_like(S_ref)

    r = r_ref[0].astype(jnp.float32)                 # [C, hd]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                 # [1, hd] -> broadcast

    cs = jnp.cumsum(lw, axis=0)                      # [C, hd]
    total = cs[-1]                                   # [hd]

    q_in = r * jnp.exp(cs - lw)                      # r_i * exp(cs_{i-1})
    k_in = k * jnp.exp(-cs)
    scores = jax.lax.dot_general(q_in, k_in, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(cols < rows, scores, 0.0)     # strictly causal
    y_intra = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)  # [C,1]
    y_intra = y_intra + diag * v

    S_in = S_ref[...]                                # [hd, hd] fp32
    y_inter = jax.lax.dot_general(q_in, S_in, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S' = diag(exp(total)) S + sum_j exp(total - cs_j) k_j v_j^T
    k_tail = k * jnp.exp(total[None, :] - cs)        # [C, hd]
    T = jax.lax.dot_general(k_tail, v, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    S_ref[...] = jnp.exp(total)[:, None] * S_in + T

    @pl.when(ic == nc - 1)
    def _emit_state():
        s_out_ref[0] = S_ref[...]


def rwkv6_scan(r, k, v, lw, u, *, chunk: int = 32, interpret: bool):
    """r/k/v: [B,S,H,hd]; lw: [B,S,H,hd] fp32 (clamped log decay);
    u: [H,hd]. Returns (y [B,S,H,hd] fp32, S_out [B,H,hd,hd] fp32)."""
    B, S, H, hd = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    # stability bound: within-chunk exponents reach chunk * |LW_MIN| and must
    # stay below fp32 exp overflow (~88); see models/rwkv6.py LW_MIN = -2.5
    assert chunk * 2.5 <= 85.0, f"chunk {chunk} breaks the decay-clamp bound"
    nc = S // chunk

    def to_bh(t):
        return jnp.moveaxis(t, 2, 1).reshape(B * H, S, -1)

    rf, kf, vf, lwf = (to_bh(t) for t in (r, k, v, lw))
    uf = u.reshape(H, 1, hd)

    def x_map(bh, ic):
        return (bh, ic, 0)

    def u_map(bh, ic):
        return (bh % H, 0, 0)

    y, s_out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), x_map),
            pl.BlockSpec((1, chunk, hd), x_map),
            pl.BlockSpec((1, chunk, hd), x_map),
            pl.BlockSpec((1, chunk, hd), x_map),
            pl.BlockSpec((1, 1, hd), u_map),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hd), x_map),
            pl.BlockSpec((1, hd, hd), lambda bh, ic: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((B * H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, lwf, uf)
    y = jnp.moveaxis(y.reshape(B, H, S, hd), 1, 2)
    return y, s_out.reshape(B, H, hd, hd)
