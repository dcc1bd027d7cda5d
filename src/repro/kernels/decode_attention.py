"""Pallas TPU decode attention: one new token against a long KV cache (the
decode_32k / long_500k serving hot path).

Grid: (batch*heads, kv_blocks); kv_blocks iterates sequentially so the
online-softmax scratch persists per (batch, head). Cache positions >=
``lengths[b]`` are masked. The cache block stream is the bandwidth-bound
working set this kernel tiles through VMEM — exactly the workload the
paper routes to bandwidth-optimized hardware (R1).
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


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, block_k: int, heads: int):
    ik = pl.program_id(1)
    nk = pl.num_programs(1)
    bh = pl.program_id(0)
    b = bh // heads

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]

    @pl.when(ik * block_k < length)
    def _step():
        q = q_ref[0].astype(jnp.float32)             # [1, hd]
        k = k_ref[0].astype(jnp.float32)             # [bk, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        idx = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        s = jnp.where(idx < length, s, NEG_INF)
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


def decode_attention(q, k_cache, v_cache, lengths, *,
                     block_k: int = 256, scale: Optional[float] = None,
                     interpret: bool):
    """q: [B,H,hd]; caches: [B,kvH,S,hd]; lengths: [B] -> [B,H,hd]."""
    B, H, hd = q.shape
    kvH, S = k_cache.shape[1], k_cache.shape[2]
    assert H % kvH == 0
    G = H // kvH
    block_k = min(block_k, S)
    assert S % block_k == 0
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    qf = q.reshape(B * H, 1, hd)
    kf = k_cache.reshape(B * kvH, S, hd)
    vf = v_cache.reshape(B * kvH, S, hd)

    def q_map(bh, ik):
        return (bh, 0, 0)

    def kv_map(bh, ik):
        b = bh // H
        h = bh % H
        return (b * kvH + h // G, ik, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_k=block_k, heads=H),
        grid=(B * H, S // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # lengths, whole array
            pl.BlockSpec((1, 1, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
        interpret=interpret,
    )(lengths.astype(jnp.int32), qf, kf, vf)
    return out.reshape(B, H, hd)


def _ragged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale: float, page: int,
                   heads: int):
    """Page-table-walking variant of ``_kernel``: the kv block for grid
    step (bh, ip) is POOL ROW ``tbl_ref[b, ip]`` (scalar-prefetched, so
    the index map can address it), and ``pl.when`` skips the step for
    pages at/after the row's length — an inactive slot (length 0) skips
    every page and never streams a byte of KV."""
    ip = pl.program_id(1)
    npg = pl.num_programs(1)
    bh = pl.program_id(0)
    b = bh // heads

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]

    @pl.when(ip * page < length)
    def _step():
        q = q_ref[0].astype(jnp.float32)             # [1, hd]
        k = k_ref[0, 0].astype(jnp.float32)          # [page, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        idx = ip * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        s = jnp.where(idx < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ip == npg - 1)
    def _finish():
        # an all-skipped row (inactive slot) has l == 0: emit zeros
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def ragged_paged_decode(q, k_pool, v_pool, tables, lengths, *,
                        scale: Optional[float] = None,
                        interpret: bool):
    """Ragged paged decode attention over a shared KV page pool.

    q: [B,H,hd] one new token per row; k_pool/v_pool: [N,kvH,page,hd]
    pooled pages (the engine's per-period pool leaf; row N-1 may be a
    trash row — it is simply never addressed because page skipping cuts
    at ``lengths``); tables: [B,P] int32 page ids per row; lengths: [B]
    valid context length (0 marks an inactive row, whose output is
    zeros and whose pages are never streamed).

    Unlike ``decode_attention`` — whose sequential kv-block grid this
    extends — the kv operand is indexed THROUGH the page table via a
    scalar-prefetched index map (``PrefetchScalarGridSpec``), so the
    bytes moved per row scale with ``ceil(length/page)`` pages instead
    of the dense ``B * S`` cache slab. Returns [B,H,hd].
    """
    B, H, hd = q.shape
    kvH, page = k_pool.shape[1], k_pool.shape[2]
    P = tables.shape[1]
    assert H % kvH == 0
    G = H // kvH
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    qf = q.reshape(B * H, 1, hd)

    def q_map(bh, ip, tbl, lens):
        return (bh, 0, 0)

    def kv_map(bh, ip, tbl, lens):
        b = bh // H
        h = bh % H
        return (tbl[b, ip], h // G, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * H, P),
        in_specs=[
            pl.BlockSpec((1, 1, hd), q_map),
            pl.BlockSpec((1, 1, page, hd), kv_map),
            pl.BlockSpec((1, 1, page, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, scale=scale, page=page, heads=H),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, 1, hd), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), qf, k_pool,
      v_pool)
    return out.reshape(B, H, hd)
