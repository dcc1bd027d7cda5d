"""Pallas TPU flash attention (prefill hot path).

Online-softmax blocked attention with explicit VMEM tiling: the grid is
(batch*heads, q_blocks, kv_blocks); kv_blocks is the innermost (sequential
on TPU) dimension, so the fp32 accumulator/max/denominator VMEM scratch
persists across kv steps for one (head, q-block). GQA is handled in the
K/V BlockSpec index maps (q head -> kv head). Causal masking skips
fully-masked kv blocks via @pl.when and masks the diagonal block in-kernel.

Target: TPU MXU — block shapes default to 128x128 over (seq, seq) with the
full head_dim kept resident; validated on CPU with interpret=True against
the pure-jnp oracle in ref.py.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, block_q: int, block_k: int, causal: bool):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    diag_ok = ((ik * block_k) <= (iq * block_q + block_q - 1)) \
        if causal else True

    @pl.when(diag_ok)
    def _step():
        q = q_ref[0].astype(jnp.float32)              # [bq, hd]
        k = k_ref[0].astype(jnp.float32)              # [bk, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = 128, block_k: int = 128,
                    scale: Optional[float] = None,
                    interpret: bool):
    """q: [B,H,S,hd]; k/v: [B,kvH,S,hd] (GQA: H % kvH == 0) -> [B,H,S,hd]."""
    B, H, S, hd = q.shape
    kvH = k.shape[1]
    assert H % kvH == 0, (H, kvH)
    G = H // kvH
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    qf = q.reshape(B * H, S, hd)
    kf = k.reshape(B * kvH, S, hd)
    vf = v.reshape(B * kvH, S, hd)

    def q_map(bh, iq, ik):
        return (bh, iq, 0)

    def kv_map(bh, iq, ik):
        b = bh // H
        h = bh % H
        return (b * kvH + h // G, ik, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal),
        grid=(B * H, S // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd)
