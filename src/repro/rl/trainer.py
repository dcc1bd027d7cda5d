"""Training-side substrate: TrainState + jit/pjit-able step functions.

``make_grpo_train_step`` is what the dry-run lowers for ``train_4k`` shapes
and what the live ActorTrain worker executes. ``make_lm_train_step`` supports
the quickstart pretraining example.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional

import jax.numpy as jnp

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.model import Model
from repro.models.moe import moe_aux_loss
from repro.optim.adamw import AdamW, AdamWState, constant
from repro.rl import losses as LO


class TrainState(NamedTuple):
    params: Any
    opt: AdamWState
    version: jnp.ndarray        # weight version (staleness protocol)


def init_train_state(model: Model, key, optimizer: AdamW) -> TrainState:
    params = model.init(key)
    return TrainState(params=params, opt=optimizer.init(params),
                      version=jnp.zeros((), jnp.int32))


def grpo_batch_spec(cfg: ModelConfig, batch: int, seq: int) -> Dict[str, Any]:
    """ShapeDtypeStructs of a GRPO training batch (used by the dry-run)."""
    f = jax.ShapeDtypeStruct
    return {
        "tokens": f((batch, seq), jnp.int32),
        "loss_mask": f((batch, seq), jnp.float32),
        "advantages": f((batch,), jnp.float32),
        "behavior_logprobs": f((batch, seq - 1), jnp.float32),
    }


def make_grpo_train_step(model: Model, optimizer: AdamW,
                         clip_eps: float = 0.2, kl_coef: float = 0.0,
                         num_microbatches: int = 1):
    """GRPO train step. ``num_microbatches > 1`` enables gradient
    accumulation inside one jit (a lax.scan over batch slices): activation
    working set scales ~1/k at the same global batch — the production fix
    for activation-bound architectures (jamba train_4k, §Perf iter 5)."""
    cfg = model.cfg

    def loss_fn(params, batch):
        lp, aux = model.forward_logprobs(params, batch["tokens"],
                                         cond=batch.get("cond"))
        loss, metrics = LO.grpo_from_logprobs(
            lp, batch["tokens"], batch["loss_mask"],
            batch["advantages"], batch["behavior_logprobs"],
            ref_logprobs=batch.get("ref_logprobs"),
            clip_eps=clip_eps, kl_coef=kl_coef)
        if cfg.uses_moe:
            if cfg.router_aux_coef or cfg.router_z_coef:
                loss = loss + moe_aux_loss(aux, cfg)
                metrics["moe_lb"] = aux["lb_loss"]
            # assignments on held experts; the busiest held expert of any
            # layer, and the mean over layers and held experts
            n_moe = cfg.num_periods * sum(f == "moe"
                                          for _, f in cfg.block_pattern)
            metrics["moe_tokens_here"] = aux["tokens_here"]
            metrics["moe_load_max"] = aux["load_max"].astype(jnp.float32)
            metrics["moe_load_mean"] = (aux["tokens_here"] / (
                n_moe * cfg.num_experts)).astype(jnp.float32)
        return loss, metrics

    def _grads(params, batch):
        return jax.value_and_grad(loss_fn, has_aux=True)(params, batch)

    def train_step(state: TrainState, batch):
        if num_microbatches <= 1:
            (loss, metrics), grads = _grads(state.params, batch)
        else:
            k = num_microbatches
            B = batch["tokens"].shape[0]
            assert B % k == 0, (B, k)

            def slice_mb(x, i):
                return jax.lax.dynamic_slice_in_dim(x, i * (B // k), B // k)

            def body(carry, i):
                grads_acc = carry
                mb = jax.tree.map(lambda x: slice_mb(x, i), batch)
                (loss, metrics), g = _grads(state.params, mb)
                grads_acc = jax.tree.map(lambda a, b: a + b, grads_acc, g)
                return grads_acc, (loss, metrics)

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            grads, (losses, metricses) = jax.lax.scan(
                body, zeros, jnp.arange(k))
            grads = jax.tree.map(lambda g: g / k, grads)
            metrics = jax.tree.map(lambda m: m.mean(), metricses)
        new_params, new_opt, gnorm = optimizer.update(grads, state.opt,
                                                      state.params)
        metrics = dict(metrics, grad_norm=gnorm)
        return TrainState(params=new_params, opt=new_opt,
                          version=state.version + 1), metrics

    return train_step


def make_lm_train_step(model: Model, optimizer: AdamW):
    cfg = model.cfg

    def loss_fn(params, batch):
        logits, aux = model.forward(params, batch["tokens"])
        loss = LO.lm_loss(logits, batch["tokens"], batch.get("mask"))
        if cfg.uses_moe:
            loss = loss + moe_aux_loss(aux, cfg)
        return loss, {"loss": loss}

    def train_step(state: TrainState, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, batch)
        new_params, new_opt, gnorm = optimizer.update(grads, state.opt,
                                                      state.params)
        return TrainState(params=new_params, opt=new_opt,
                          version=state.version + 1), dict(metrics,
                                                           grad_norm=gnorm)

    return train_step


def make_logprob_fn(model: Model):
    """Recompute per-token logprobs of given trajectories under ``params``
    (used for ref/behavior logprobs on the training side)."""
    def logprob_fn(params, tokens):
        logits, _ = model.forward(params, tokens)
        return LO.token_logprobs(logits, tokens)
    return logprob_fn


def default_optimizer(lr: float = 3e-4) -> AdamW:
    return AdamW(lr=constant(lr))
