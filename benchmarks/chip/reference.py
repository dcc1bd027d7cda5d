"""Plain float32 reference of a decoder and of the GRPO step.

The decoder of each configuration comes from
``reference_models/<model_type>.py`` (its ``init_params`` and ``logits``),
written from the published architecture; this file holds what every
family shares: float32 matmuls at ``highest`` precision, RMSNorm, rotary
embeddings, the token-level clipped GRPO loss and AdamW with global-norm
clipping, as the launcher trains (``--lr`` 1e-3, AdamW's published
defaults, ratio clip 0.2). No cache, no kernels, no batching tricks.

It imports nothing of the system under test. Its weights are made from
the seed by the seeding recipe the system's initializer follows, so both
sides hold the same numbers from the same seed without either handing
the other anything.

``lowp=True`` is the control: the same computation with every matmul
operand (weights and activations) rounded to float8 e4m3 under one
per-tensor scale, the step below bfloat16 that a serving or training
change could be tempted to take; gradients pass the rounding straight
through.
"""
from __future__ import annotations

import functools
import json
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.spec import load_module

MODELS = Path(__file__).resolve().parent / "reference_models"
FP8_MAX = 448.0          # largest finite float8 e4m3 value
# the optimizer and loss the launcher trains with
ADAMW = {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
         "weight_decay": 0.1, "clip_norm": 1.0}
CLIP_EPS = 0.2


def seed_key(seed: int):
    """The PRNG key both sides derive from ``--seed`` (its low 32 bits)."""
    return jax.random.PRNGKey(seed & 0xFFFFFFFF)


@functools.cache
def family(model_type: str):
    """The module ``reference_models/<model_type>.py``."""
    path = MODELS / f"{model_type}.py"
    if not path.is_file():
        raise ValueError(f"no reference model for model_type "
                         f"{model_type!r} under {MODELS}")
    return load_module(path, f"benchmarks.chip.reference_models.{model_type}")


def init_params(cfg: Dict, seed: int) -> Dict:
    """float32 weights of the configuration's decoder from the seed."""
    return family(cfg["model_type"]).init_params(cfg, seed)


def logits(cfg: Dict, params: Dict, tokens, lowp: bool = False):
    """Teacher-forced logits [B, S, V] of ``tokens`` [B, S]."""
    return family(cfg["model_type"]).logits(cfg, params, tokens, lowp)


def _fp8(x):
    """Round to float8 e4m3 under one per-tensor scale, back in f32. The
    gradient passes straight through in f32, as in float8 training
    recipes, which keep the backward pass out of e4m3's range."""
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def mm(eq, a, b, lowp):
    if lowp:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + eps) * scale


def rope(x, theta):
    """x: [B, S, H, hd]; half-split rotation at positions 0..S-1."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = np.arange(x.shape[1], dtype=np.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def token_logprobs(cfg: Dict, params: Dict, tokens, lowp: bool = False):
    """log p(tokens[:, t+1] | tokens[:, :t+1]) as [B, S-1]."""
    lg = jax.nn.log_softmax(logits(cfg, params, tokens, lowp)[:, :-1], -1)
    return jnp.take_along_axis(lg, tokens[:, 1:, None], -1)[..., 0]


def grpo_loss(cfg: Dict, params: Dict, batch: Dict, lowp: bool = False):
    """Token-level clipped policy-gradient loss over the action tokens
    (``loss_mask``), one advantage per row, ratio against the behaviour
    logprobs the rollout recorded."""
    lp = token_logprobs(cfg, params, batch["tokens"], lowp)
    m = batch["loss_mask"][:, 1:]
    adv = batch["advantages"][:, None]
    ratio = jnp.exp(lp - batch["behavior_logprobs"])
    pg = -jnp.minimum(ratio * adv,
                      jnp.clip(ratio, 1 - CLIP_EPS, 1 + CLIP_EPS) * adv)
    return jnp.sum(pg * m) / jnp.maximum(jnp.sum(m), 1.0)


def adamw(params, grads, mu, nu, step: int, opt: Dict = ADAMW):
    """One AdamW step with global-norm clipping and decoupled weight decay
    on matrices (leaves of two or more dimensions)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

    def upd(p, m, v):
        u = (m / (1 - b1 ** step)) / (jnp.sqrt(v / (1 - b2 ** step))
                                      + opt["eps"])
        if p.ndim >= 2:
            u = u + opt["weight_decay"] * p
        return p - opt["lr"] * u

    return jax.tree.map(upd, params, mu, nu), grads, mu, nu


def leaf_norms(tree) -> Dict[str, float]:
    """Frobenius norm of every leaf, keyed by its path."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): float(jnp.linalg.norm(v.ravel()))
            for k, v in flat}


@functools.cache
def _programs(cfg_json: str, lowp: bool):
    """The jitted gradient, AdamW and served-logprob programs of one
    configuration, built once a process."""
    cfg = json.loads(cfg_json)
    grad = jax.jit(jax.value_and_grad(
        lambda p, b: grpo_loss(cfg, p, b, lowp)))
    step = jax.jit(adamw, static_argnums=4)
    served = jax.jit(lambda p, t: token_logprobs(cfg, p, t, lowp))
    return grad, step, served


def programs(cfg: Dict, lowp: bool):
    return _programs(json.dumps(cfg, sort_keys=True), lowp)


def follow_training(cfg: Dict, seed: int, batches: Sequence[Dict],
                    lowp: bool = False
                    ) -> Tuple[List[float], Dict[str, float],
                               Dict[str, float]]:
    """Train from the seed's weights through ``batches``, one AdamW step
    each. Returns the loss of every step, the norm of each leaf's
    first (clipped) gradient and the norm of each leaf's change over all
    the steps."""
    grad_fn, step_fn, _ = programs(cfg, lowp)
    with jax.default_matmul_precision("highest"):
        params = start = init_params(cfg, seed)
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        losses, first = [], None
        for t, batch in enumerate(batches, start=1):
            b = {k: jnp.asarray(v) for k, v in batch.items()}
            loss, grads = grad_fn(params, b)
            params, clipped, mu, nu = step_fn(params, grads, mu, nu, t)
            losses.append(float(loss))
            if first is None:
                first = leaf_norms(clipped)
        change = leaf_norms(jax.tree.map(jnp.subtract, params, start))
    return losses, first, change


def served_logprobs(cfg: Dict, params: Dict, turns: Sequence[Tuple],
                    length: int, rows: int = 8, lowp: bool = False
                    ) -> List[np.ndarray]:
    """Teacher-forced logprobs of each turn's served tokens. A turn is
    ``(tokens, start, engine_logprobs)``: the context the request carried
    followed by the served tokens from index ``start``. Turns run in
    blocks of ``rows``, padded to ``length``."""
    fn = programs(cfg, lowp)[2]
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(turns), rows):
            group = turns[i:i + rows]
            toks = np.zeros((rows, length), np.int32)
            for r, (seq, _, _) in enumerate(group):
                toks[r, :len(seq)] = seq
            lp = np.asarray(fn(params, jnp.asarray(toks)))
            for r, (seq, start, served) in enumerate(group):
                out.append(lp[r, start - 1:start - 1 + len(served)])
    return out
