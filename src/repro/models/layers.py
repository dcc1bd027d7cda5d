"""Core transformer layers: RMSNorm, RoPE, GQA attention (qk-norm, sliding
window, KV-cache decode), gated MLP. Pure-function style: ``init_*`` builds a
param dict, ``*_fwd`` applies it. All matmuls run in ``cfg.dtype`` with
fp32 softmax/norm accumulation.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shd

NEG_INF = -1e30


def dt(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def pdt(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def init_rmsnorm(dim: int, dtype, zero_centered: bool = False) -> dict:
    """A zero-centred norm scales by ``1 + scale``, its scale starting at
    0 (so weight decay pulls it toward a scale of 1, not 0)."""
    return {"scale": (jnp.zeros if zero_centered else jnp.ones)((dim,),
                                                                 dtype)}


def _norm_scale(scale, zero_centered: bool):
    scale = scale.astype(jnp.float32)
    return 1.0 + scale if zero_centered else scale


def rmsnorm(p, x, eps: float, zero_centered: bool = False):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * _norm_scale(p["scale"], zero_centered)).astype(x.dtype)


def rmsnorm_head(scale, x, eps: float, zero_centered: bool = False):
    """qk-norm over the head dim; scale shape [head_dim]."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * _norm_scale(scale, zero_centered)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float, rotary_dim: Optional[int] = None):
    """x: [..., S, hd]; positions: broadcastable to [..., S]. With
    ``rotary_dim`` < hd only the first ``rotary_dim`` dims rotate
    (frequencies over ``rotary_dim``); the rest pass through."""
    hd = x.shape[-1]
    if rotary_dim is not None and rotary_dim < hd:
        rot = apply_rope(x[..., :rotary_dim], positions, theta)
        return jnp.concatenate([rot, x[..., rotary_dim:]], axis=-1)
    freqs = rope_freqs(hd, theta)                       # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 5 if cfg.attn_output_gate else 4)
    p = {
        "wq": dense_init(ks[0], (d, cfg.num_heads, hd), pdt(cfg)),
        "wk": dense_init(ks[1], (d, cfg.num_kv_heads, hd), pdt(cfg)),
        "wv": dense_init(ks[2], (d, cfg.num_kv_heads, hd), pdt(cfg)),
        "wo": dense_init(ks[3], (cfg.num_heads, hd, d), pdt(cfg),
                         scale=1.0 / math.sqrt(cfg.num_heads * hd)),
    }
    if cfg.attn_output_gate:
        # the per-head output gate (stored interleaved with q in HF's
        # q_proj; a separate matrix here)
        p["wg"] = dense_init(ks[4], (d, cfg.num_heads, hd), pdt(cfg))
    if cfg.qk_norm:
        init = jnp.zeros if cfg.zero_centered_norm else jnp.ones
        p["q_norm"] = init((hd,), pdt(cfg))
        p["k_norm"] = init((hd,), pdt(cfg))
    return p


def _qkv(p, cfg: ModelConfig, x, positions):
    """q/k/v projections, qk-norm and rotary embeddings. x: [B,S,d];
    positions [B,S], or [B] for one decode token per row. Returns
    [B,H,S,hd] q, [B,kvH,S,hd] k and v."""
    cdt = dt(cfg)
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dnh->bsnh", x, p["wv"].astype(cdt))
    if cfg.qk_norm:
        zc = cfg.zero_centered_norm
        q = rmsnorm_head(p["q_norm"], q, cfg.norm_eps, zc)
        k = rmsnorm_head(p["k_norm"], k, cfg.norm_eps, zc)
    rot = cfg.rotary_dim if cfg.partial_rotary_factor < 1 else None

    def pos():
        return (positions[:, None, :] if positions.ndim == 2
                else positions[:, None, None])
    q = apply_rope(q.swapaxes(1, 2), pos(), cfg.rope_theta, rot)
    k = apply_rope(k.swapaxes(1, 2), pos(), cfg.rope_theta, rot)
    return q, k, v.swapaxes(1, 2)



def attention_out(p, cfg: ModelConfig, x, out):
    """Output projection of attention heads ``out`` [B,H,S,hd] computed
    from ``x`` [B,S,d]; with ``attn_output_gate`` each head's output is
    first scaled by ``sigmoid(x . wg)``."""
    cdt = dt(cfg)
    if cfg.attn_output_gate:
        g = jnp.einsum("bsd,dnh->bnsh", x, p["wg"].astype(cdt))
        out = (out.astype(jnp.float32)
               * jax.nn.sigmoid(g.astype(jnp.float32))).astype(cdt)
    return jnp.einsum("bnsh,nhd->bsd", out, p["wo"].astype(cdt))


def _grouped_scores(q, k, cfg: ModelConfig):
    """q: [B,H,S,hd], k: [B,kvH,T,hd] -> scores [B,kvH,G,S,T] (fp32)."""
    B, H, S, hd = q.shape
    G = H // cfg.num_kv_heads
    qg = q.reshape(B, cfg.num_kv_heads, G, S, hd)
    scores = jnp.einsum("bkgsh,bkth->bkgst", qg, k,
                        preferred_element_type=jnp.float32)
    return scores / math.sqrt(hd)


def _attend_causal(q, k, v, cfg: ModelConfig, window: Optional[int],
                   q_chunk: int = 1024):
    """Causal attention over full K/V, blocked over the query dim so the
    [S,S] score matrix is never materialized (the XLA-path analogue of the
    Pallas flash-attention kernel). q: [B,H,S,hd]; k/v: [B,kvH,S,hd]."""
    B, H, S, hd = q.shape
    G = H // cfg.num_kv_heads
    cq = min(q_chunk, S)
    while S % cq:
        cq -= 1
    nb = S // cq
    qg = q.reshape(B, cfg.num_kv_heads, G, nb, cq, hd)
    j = jnp.arange(S)[None, :]

    def block(carry, xs):
        qb, blk = xs                                     # [B,kvH,G,cq,hd]
        i = blk * cq + jnp.arange(cq)[:, None]
        scores = jnp.einsum("bkgsh,bkth->bkgst", qb, k,
                            preferred_element_type=jnp.float32)
        scores = scores / math.sqrt(hd)
        mask = j <= i
        if window is not None:
            mask &= (i - j) < window
        scores = jnp.where(mask, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        ob = jnp.einsum("bkgst,bkth->bkgsh", probs, v)
        return carry, ob

    if nb == 1:
        _, out = block(None, (qg[:, :, :, 0], jnp.int32(0)))
        out = out[:, :, :, None]
    else:
        _, out = jax.lax.scan(jax.checkpoint(block), None,
                              (jnp.moveaxis(qg, 3, 0), jnp.arange(nb)))
        out = jnp.moveaxis(out, 0, 3)                    # [B,kvH,G,nb,cq,hd]
    return out.reshape(B, H, S, hd)


def attention_fwd(p, cfg: ModelConfig, x, positions,
                  window: Optional[int] = None, q_chunk: int = 1024):
    """Full-sequence causal attention. x: [B,S,d], positions: [B,S]."""
    B, S, _ = x.shape
    q, k, v = _qkv(p, cfg, x, positions)
    q = shd(q, "batch", "act_heads", "seq", None)
    out = _attend_causal(q, k, v, cfg, window, q_chunk=q_chunk)
    out = shd(out, "batch", "act_heads", "seq", None)
    return attention_out(p, cfg, x, out)


def attention_decode(p, cfg: ModelConfig, x, k_cache, v_cache, positions,
                     lengths, window: Optional[int] = None):
    """One-token decode against a KV cache.

    x: [B,1,d]; k_cache/v_cache: [B,kvH,S_cache,hd]; positions: [B] absolute
    position of the new token; lengths: [B] valid cache length (== positions
    for dense cache). With ``window`` the cache is a ring buffer of size
    S_cache==window and slots are addressed mod window.

    Returns (out [B,1,d], k_cache, v_cache) with the new K/V written in.
    """
    B = x.shape[0]
    S_cache = k_cache.shape[2]
    cdt = dt(cfg)
    # k_new, v_new: [B,kvH,1,hd]
    q, k_new, v_new = _qkv(p, cfg, x, positions)

    slot = positions % S_cache if window is not None else positions
    onehot = jax.nn.one_hot(slot, S_cache, dtype=cdt)     # [B,S_cache]
    # PERF(iter 2b, decode): keep the write mask sharded like the cache seq
    # axis, otherwise GSPMD materializes a fully-gathered cache around the
    # elementwise update
    onehot = shd(onehot, "batch", "cache_seq")
    k_cache = k_cache * (1 - onehot[:, None, :, None]) + \
        onehot[:, None, :, None] * k_new
    v_cache = v_cache * (1 - onehot[:, None, :, None]) + \
        onehot[:, None, :, None] * v_new
    k_cache = shd(k_cache, "batch", "kv_heads", "cache_seq", None)
    v_cache = shd(v_cache, "batch", "kv_heads", "cache_seq", None)

    scores = _grouped_scores(q, k_cache, cfg)             # [B,kvH,G,1,S_cache]
    # PERF(iter 2, decode): keep scores sharded over the cache-seq axis so
    # softmax stats + PV partials all-reduce ~100 KB/layer instead of
    # all-gathering the multi-GB KV cache
    scores = shd(scores, "batch", None, None, None, "cache_seq")
    idx = jnp.arange(S_cache)[None, :]                    # [1,S_cache]
    if window is not None:
        age = positions[:, None] - \
            (idx + ((positions[:, None] - idx) // S_cache) * S_cache)
        valid = (age >= 0) & (age < jnp.minimum(lengths + 1, S_cache)[:, None])
    else:
        valid = idx <= positions[:, None]
        valid &= idx < jnp.maximum(lengths + 1, 1)[:, None]
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(cdt)
    out = jnp.einsum("bkgst,bkth->bkgsh", probs, v_cache)
    out = out.reshape(B, cfg.num_heads, 1, cfg.head_dim)
    return attention_out(p, cfg, x, out), k_cache, v_cache


def attention_decode_paged(p, cfg: ModelConfig, x, k_pool, v_pool, tables,
                           positions, page_size: int):
    """One-token decode against a PAGED KV pool.

    x: [B,1,d]; k_pool/v_pool: [N+1, kvH, page, hd] (row N is the trash
    page absorbing padded rows' writes); tables: [B,P] int32 page ids
    (padded with the trash id); positions: [B] absolute position of the
    new token. ``P * page_size`` must equal the dense engine's
    ``max_len``: the gather below always materializes the FULL table
    width, so the attention reduction runs over exactly the same axis
    length — and therefore exactly the same partial-sum grouping — as
    ``attention_decode``. Masked positions contribute exact zeros either
    way, which is what makes paged-vs-dense greedy decode byte-identical
    (the perf win is batch compaction: B is the POW2-bucketed ACTIVE
    slot count, not max_slots). The Pallas counterpart that also skips
    empty pages is ``kernels.decode_attention.ragged_paged_decode``.

    Returns (out [B,1,d], k_pool, v_pool) with the new K/V scattered
    into each row's current page.
    """
    B = x.shape[0]
    P = tables.shape[1]
    kvH, hd = k_pool.shape[1], k_pool.shape[3]
    cdt = dt(cfg)
    # k_new, v_new: [B,kvH,1,hd]
    q, k_new, v_new = _qkv(p, cfg, x, positions)

    # scatter the new K/V at (page_table[b, pos//page], pos%page): the
    # write VALUE is the same bits attention_decode's one-hot update
    # produces (0*old + 1*new == new)
    pid = jnp.take_along_axis(tables, (positions // page_size)[:, None],
                              axis=1)[:, 0]               # [B]
    off = positions % page_size
    k_pool = k_pool.at[pid, :, off, :].set(k_new[:, :, 0, :])
    v_pool = v_pool.at[pid, :, off, :].set(v_new[:, :, 0, :])
    k_pool = shd(k_pool, None, "cache_kv_heads", "cache_page_seq", None)
    v_pool = shd(v_pool, None, "cache_kv_heads", "cache_page_seq", None)

    # gather each row's pages to a dense [B,kvH,P*page,hd] view
    kg = jnp.moveaxis(k_pool[tables], 2, 1).reshape(B, kvH, P * page_size, hd)
    vg = jnp.moveaxis(v_pool[tables], 2, 1).reshape(B, kvH, P * page_size, hd)

    scores = _grouped_scores(q, kg, cfg)                  # [B,kvH,G,1,T]
    idx = jnp.arange(P * page_size)[None, :]
    valid = idx <= positions[:, None]
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(cdt)
    out = jnp.einsum("bkgst,bkth->bkgsh", probs, vg)
    out = out.reshape(B, cfg.num_heads, 1, cfg.head_dim)
    return attention_out(p, cfg, x, out), k_pool, v_pool


# ---------------------------------------------------------------------------
# Gated MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d, f), pdt(cfg)),
        "w_up": dense_init(ks[1], (d, f), pdt(cfg)),
        "w_down": dense_init(ks[2], (f, d), pdt(cfg)),
    }


def mlp_fwd(p, cfg: ModelConfig, x):
    cdt = dt(cfg)
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(cdt))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(cdt))
    h = jax.nn.silu(g) * u
    h = shd(h, "batch", "seq", "act_mlp")
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(cdt))
