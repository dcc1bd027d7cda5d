"""Mixture-of-Experts layer: dropless top-k routing over the experts this
chip holds.

The router scores all ``cfg.num_router_experts`` experts (softmax over
them, top-k, renormalised over the k). The layer holds ``cfg.num_experts``
of them, router ids ``[expert_offset, expert_offset + num_experts)``, and
computes only their part of the result: one chip's share of an
expert-parallel layer, run without its exchange. A layer that holds every
expert computes the whole layer.

No assignment is dropped. The token-expert assignments are sorted by held
expert, those of experts held elsewhere last, and each expert matrix is
one grouped matmul over the sorted rows (``jax.lax.ragged_dot``); rows
past the last group are zeroed. Long inputs (the train step) run
in blocks of ``TOKEN_BLOCK`` tokens, each rematerialised in the backward
pass, so a block's sorted copy of the tokens is the largest buffer.

An optional shared expert runs on every token, scaled by
``sigmoid(x . w)`` where ``cfg.shared_expert_gate``.

Aux losses: load-balance (Switch-style) and router z-loss over the
router's experts. Counters (in ``aux``): ``tokens_here``, assignments
that landed on held experts; ``load_max``, the most that one held expert
took; ``touched``, held experts that took at least one.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shd
from repro.models.layers import dense_init, dt, pdt

TOKEN_BLOCK = 4096


def init_moe(key, cfg: ModelConfig) -> dict:
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.num_experts
    ks = jax.random.split(key, 8 if cfg.shared_expert_gate else 7)
    p = {
        "router": dense_init(ks[0], (d, cfg.num_router_experts), jnp.float32,
                             scale=0.02),
        # each expert's matrices scaled by their own fan-in (d, then f)
        "w_gate": dense_init(ks[1], (e, d, f), pdt(cfg),
                             scale=1.0 / math.sqrt(d)),
        "w_up": dense_init(ks[2], (e, d, f), pdt(cfg),
                           scale=1.0 / math.sqrt(d)),
        "w_down": dense_init(ks[3], (e, f, d), pdt(cfg),
                             scale=1.0 / math.sqrt(f)),
    }
    if cfg.num_shared_experts:
        fs = f * cfg.num_shared_experts
        p["shared_gate"] = dense_init(ks[4], (d, fs), pdt(cfg))
        p["shared_up"] = dense_init(ks[5], (d, fs), pdt(cfg))
        p["shared_down"] = dense_init(ks[6], (fs, d), pdt(cfg))
    if cfg.shared_expert_gate:
        p["shared_expert_gate"] = dense_init(ks[7], (d, 1), pdt(cfg))
    return p


def _experts(p, cfg: ModelConfig, x, eid, w):
    """The held experts' part for tokens x [T,d]: ``eid`` [T,K] is each
    assignment's held expert (``num_experts`` where it is held
    elsewhere), ``w`` [T,K] its weight (0 there)."""
    T, d = x.shape
    E, K = cfg.num_experts, cfg.top_k
    cdt = dt(cfg)
    flat = eid.reshape(T * K)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((E + 1,), jnp.int32).at[flat].add(1)[:E]
    # the grouped matmul leaves rows past the last group undefined on the
    # TPU (zeros on the CPU): zero them on the way in and out, so neither
    # values nor cotangents of assignments held elsewhere leak in
    rows = (jnp.arange(T * K) < jnp.sum(sizes))[:, None]

    def grouped(lhs, w):
        return jnp.where(rows, jax.lax.ragged_dot(lhs, w.astype(cdt), sizes),
                         0)
    xs = jnp.where(rows, x.astype(cdt)[order // K], 0)    # [T*K, d]
    h = jax.nn.silu(grouped(xs, p["w_gate"])) * grouped(xs, p["w_up"])
    ys = grouped(h, p["w_down"])                          # [T*K, d]
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * K, dtype=order.dtype))
    y = jnp.einsum("tkd,tk->td", ys[inv].reshape(T, K, d), w.astype(cdt),
                   preferred_element_type=jnp.float32)
    return y.astype(cdt)


def moe_fwd(p, cfg: ModelConfig, x, live=None) -> Tuple[jnp.ndarray, dict]:
    """x: [B,S,d] -> (y [B,S,d], aux). ``live`` [B,S] bool routes only
    those tokens to the experts (decode passes the rows still emitting).

    aux: ``lb_loss``, ``z_loss``, and the int32 counters ``tokens_here``,
    ``load_max``, ``touched``."""
    B, S, d = x.shape
    T = B * S
    E, K, R = cfg.num_experts, cfg.top_k, cfg.num_router_experts
    xf = x.reshape(T, d)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))   # [T,R]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, K)                # [T,K]
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)
    local = idx - cfg.expert_offset
    held = (local >= 0) & (local < E)
    if live is not None:
        held &= live.reshape(T, 1)
    eid = jnp.where(held, local, E)
    w = jnp.where(held, gate_vals, 0.0)
    if T <= TOKEN_BLOCK:
        y = _experts(p, cfg, xf, eid, w)
    else:
        nb = -(-T // TOKEN_BLOCK)
        pad = nb * TOKEN_BLOCK - T

        def blocks(a, fill):
            a = jnp.pad(a, [(0, pad), (0, 0)], constant_values=fill)
            return a.reshape((nb, TOKEN_BLOCK) + a.shape[1:])

        def block(_, xs):
            return None, _experts(p, cfg, *xs)
        _, y = jax.lax.scan(jax.checkpoint(block), None,
                            (blocks(xf, 0), blocks(eid, E), blocks(w, 0)))
        y = y.reshape(nb * TOKEN_BLOCK, d)[:T]
    y = y.reshape(B, S, d)

    if cfg.num_shared_experts:
        cdt = dt(cfg)
        gs = jnp.einsum("bsd,df->bsf", x, p["shared_gate"].astype(cdt))
        us = jnp.einsum("bsd,df->bsf", x, p["shared_up"].astype(cdt))
        hs = jax.nn.silu(gs) * us
        hs = shd(hs, "batch", "seq", "act_mlp")
        ys = jnp.einsum("bsf,fd->bsd", hs, p["shared_down"].astype(cdt))
        if cfg.shared_expert_gate:
            sg = jnp.einsum("bsd,do->bso", x,
                            p["shared_expert_gate"].astype(cdt))
            ys = (ys.astype(jnp.float32)
                  * jax.nn.sigmoid(sg.astype(jnp.float32))).astype(cdt)
        y = y + ys

    me = probs.mean(0)
    ce = jnp.zeros((R,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (T * K)
    sizes = jnp.zeros((E + 1,), jnp.int32).at[eid.reshape(-1)].add(1)[:E]
    aux = {"lb_loss": R * jnp.sum(me * ce),
           "z_loss": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
           "tokens_here": jnp.sum(sizes), "load_max": jnp.max(sizes),
           "touched": jnp.sum((sizes > 0).astype(jnp.int32))}
    return y, aux


def moe_aux_loss(aux: dict, cfg: ModelConfig):
    return (cfg.router_aux_coef * aux["lb_loss"]
            + cfg.router_z_coef * aux["z_loss"])
