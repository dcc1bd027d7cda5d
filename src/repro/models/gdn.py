"""Gated DeltaNet linear attention (the linear layers of Qwen3-Next).

Per token, with ``S`` a [dk, dv] float32 state per value head:

    S <- exp(g_t) S;  S <- S + k_t (beta_t (v_t - S^T k_t))^T;  o_t = S^T q_t

where q/k/v come from a causal depthwise conv (width ``K``) and SiLU over
their projections, q and k are L2-normalised per head (q also scaled by
1/sqrt(dk)) and repeated from the key heads to the value heads,
``beta = sigmoid(x.w_b)`` and ``g = -exp(A_log) softplus(x.w_a + dt_bias)``.
The output is a gated RMSNorm per head, ``w norm(o) silu(z)``, and a
projection back to d.

Prefill and the train step use the chunked (WY) form of the rule, with
chunks of ``CHUNK`` tokens: inside a chunk the rule is one triangular
solve and a few matmuls, and only the chunk boundaries are sequential.
Decode uses the per-token step. The state and the decay cumsum are float32;
the chunk matmuls take their operands in the configuration's dtype and
accumulate in float32. A position with ``beta = 0`` and ``g = 0`` leaves
the state as it was, which is how padded prompt positions are masked.

The cache of a layer is ``S`` [B, Hv, dk, dv] float32 and ``conv``
[B, K-1, conv_dim], the last K-1 conv inputs.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init, dt, pdt

CHUNK = 64
L2_EPS = 1e-6
# rows a train-step group holds at once (``gdn_rows``)
ROW_GROUP = 8


def init_gdn(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    kc = cfg.linear_conv_kernel_dim
    ks = jax.random.split(key, 9)
    return {
        "wq": dense_init(ks[0], (d, hk, dk), pdt(cfg)),
        "wk": dense_init(ks[1], (d, hk, dk), pdt(cfg)),
        "wv": dense_init(ks[2], (d, hv, dv), pdt(cfg)),
        "wz": dense_init(ks[3], (d, hv, dv), pdt(cfg)),
        "wb": dense_init(ks[4], (d, hv), pdt(cfg)),
        "wa": dense_init(ks[5], (d, hv), pdt(cfg)),
        # depthwise conv taps [K, channels]; tap K-1 meets the current token
        "conv": dense_init(ks[6], (kc, cfg.gdn_conv_dim), pdt(cfg),
                           scale=1.0 / math.sqrt(kc)),
        "A_log": jnp.log(jax.random.uniform(ks[7], (hv,), jnp.float32,
                                            1.0, 16.0)).astype(pdt(cfg)),
        "dt_bias": jnp.ones((hv,), pdt(cfg)),
        "norm": jnp.ones((dv,), pdt(cfg)),
        "wo": dense_init(ks[8], (hv, dv, d), pdt(cfg),
                         scale=1.0 / math.sqrt(hv * dv)),
    }


def _project(p, cfg: ModelConfig, x):
    """x [B,S,d] -> conv input [B,S,conv_dim] (q, k, v side by side),
    z [B,S,Hv,dv], beta and g [B,S,Hv] (float32)."""
    cdt = dt(cfg)
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dnh->bsnh", x, p["wv"].astype(cdt))
    z = jnp.einsum("bsd,dnh->bsnh", x, p["wz"].astype(cdt))
    b = jnp.einsum("bsd,dn->bsn", x, p["wb"].astype(cdt))
    a = jnp.einsum("bsd,dn->bsn", x, p["wa"].astype(cdt))
    mixed = jnp.concatenate([q.reshape(B, S, -1), k.reshape(B, S, -1),
                             v.reshape(B, S, -1)], axis=-1)
    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    return mixed, z, beta, g


def _heads(cfg: ModelConfig, conv_out):
    """SiLU'd conv output [B,S,conv_dim] float32 -> q, k [B,S,Hv,dk]
    (normalised, q scaled, repeated to the value heads) and v [B,S,Hv,dv],
    in the configuration's dtype, as the chunk matmuls take them."""
    B, S, _ = conv_out.shape
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    kd = cfg.gdn_key_dim
    q = conv_out[..., :kd].reshape(B, S, hk, dk)
    k = conv_out[..., kd:2 * kd].reshape(B, S, hk, dk)
    v = conv_out[..., 2 * kd:].reshape(B, S, hv, dv)

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)
    cdt = dt(cfg)
    q = (l2(q) / math.sqrt(dk)).astype(cdt)
    k = l2(k).astype(cdt)
    rep = hv // hk
    return (jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2),
            v.astype(cdt))


def _gated_norm(p, cfg: ModelConfig, o, z):
    """o [..., Hv, dv] float32, z the gate -> w norm(o) silu(z), cdt."""
    var = jnp.mean(o * o, -1, keepdims=True)
    y = o * jax.lax.rsqrt(var + cfg.norm_eps) * p["norm"].astype(jnp.float32)
    return (y * jax.nn.silu(z.astype(jnp.float32))).astype(dt(cfg))


def _mm(eq, a, b, cdt):
    return jnp.einsum(eq, a.astype(cdt), b.astype(cdt),
                      preferred_element_type=jnp.float32)


def chunk_delta_rule(q, k, v, g, beta, state, cdt, chunk: int = CHUNK):
    """The gated delta rule over a sequence, chunk by chunk (WY form).

    q, k: [B,S,H,dk]; v: [B,S,H,dv]; g, beta: [B,S,H] float32; state
    [B,H,dk,dv] float32. Returns (o [B,S,H,dv] float32, final state)."""
    B, S, H, dk = k.shape
    dv = v.shape[-1]
    C = min(chunk, S)
    pad = -S % C
    if pad:
        # beta = g = 0 past the end: the state passes through unchanged
        q, k, v, g, beta = (jnp.pad(t, [(0, 0), (0, pad)]
                                    + [(0, 0)] * (t.ndim - 2))
                            for t in (q, k, v, g, beta))
    n = (S + pad) // C

    def chunks(t):          # [B,S,H,...] -> [n,B,H,C,...]
        t = t.reshape((B, n, C) + t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 1, 0), 3, 2)
    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    tril = jnp.tril(jnp.ones((C, C), bool))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    eye = jnp.eye(C, dtype=jnp.float32)

    def body(s, xs):
        qc, kc, vc, gc, bc = xs                  # [B,H,C,...]
        gc = jnp.cumsum(gc, axis=-1)             # float32 decay cumsum
        diff = gc[..., :, None] - gc[..., None, :]
        decay = jnp.where(tril, jnp.exp(jnp.where(tril, diff, 0.0)), 0.0)
        kb = kc * bc[..., None]
        a = jnp.where(strict, _mm("bhik,bhjk->bhij", kb, kc, cdt) * decay,
                      0.0)
        # T = (I + A)^-1, A strictly lower: the WY representation
        t = jax.scipy.linalg.solve_triangular(
            eye + a, jnp.broadcast_to(eye, a.shape), lower=True,
            unit_diagonal=True)
        u = _mm("bhij,bhjv->bhiv", t, vc * bc[..., None], cdt)
        w = _mm("bhij,bhjk->bhik", t, kb * jnp.exp(gc)[..., None], cdt)
        v_new = u - _mm("bhik,bhkv->bhiv", w, s, cdt)
        attn = jnp.where(tril, _mm("bhik,bhjk->bhij", qc, kc, cdt) * decay,
                         0.0)
        o = (_mm("bhik,bhkv->bhiv", qc * jnp.exp(gc)[..., None], s, cdt)
             + _mm("bhij,bhjv->bhiv", attn, v_new, cdt))
        last = gc[..., -1:]
        s = (s * jnp.exp(last)[..., None]
             + _mm("bhik,bhiv->bhkv", kc * jnp.exp(last - gc)[..., None],
                   v_new, cdt))
        return s, o

    state, o = jax.lax.scan(jax.checkpoint(body), state,
                            (q, k, v, g, beta))
    o = jnp.moveaxis(o, 0, 1).swapaxes(2, 3).reshape(B, n * C, H, dv)
    return o[:, :S], state


def delta_rule_step(q, k, v, g, beta, state):
    """One token of the gated delta rule, float32 and elementwise. q, k:
    [B,H,dk]; v: [B,H,dv]; g, beta: [B,H]; state [B,H,dk,dv]."""
    state = state * jnp.exp(g)[..., None, None]
    mem = jnp.sum(state * k[..., None], axis=-2)          # S^T k
    delta = beta[..., None] * (v - mem)
    state = state + k[..., None] * delta[..., None, :]
    return jnp.sum(state * q[..., None], axis=-2), state


def gdn_fwd(p, cfg: ModelConfig, x, valid=None, last_pos=None):
    """Full-sequence Gated DeltaNet. x: [B,S,d]; ``valid`` [B,S] marks
    real positions (a padded prompt's tail is masked out of the conv
    state and the recurrence); ``last_pos`` [B] is each row's last real
    position. Returns (y [B,S,d], cache {"S", "conv"} at ``last_pos``,
    default S-1)."""
    B, S, _ = x.shape
    kc = cfg.linear_conv_kernel_dim
    mixed, z, beta, g = _project(p, cfg, x)
    if valid is not None:
        beta = jnp.where(valid[..., None], beta, 0.0)
        g = jnp.where(valid[..., None], g, 0.0)
    xpad = jnp.pad(mixed, [(0, 0), (kc - 1, 0), (0, 0)])
    w = p["conv"].astype(jnp.float32)
    conv = sum(xpad[:, j:j + S].astype(jnp.float32) * w[j]
               for j in range(kc))
    q, k, v = _heads(cfg, jax.nn.silu(conv))
    state0 = jnp.zeros((B, cfg.linear_num_value_heads,
                        cfg.linear_key_head_dim, cfg.linear_value_head_dim),
                       jnp.float32)
    o, state = chunk_delta_rule(q, k, v, g, beta, state0, dt(cfg))
    y = jnp.einsum("bsnh,nhd->bsd", _gated_norm(p, cfg, o, z),
                   p["wo"].astype(dt(cfg)))
    if last_pos is None:
        last_pos = jnp.full((B,), S - 1, jnp.int32)
    # the K-1 conv inputs up to and including last_pos (xpad is shifted
    # by K-1, so they start at xpad index last_pos + 1)
    tail = jax.vmap(lambda r, i: jax.lax.dynamic_slice_in_dim(
        r, i + 1, kc - 1, axis=0))(xpad, last_pos.astype(jnp.int32))
    return y, {"S": state, "conv": tail}


def gdn_decode(p, cfg: ModelConfig, x, state, conv_state):
    """One token per row. x: [B,1,d]; state [B,Hv,dk,dv] float32;
    conv_state [B,K-1,conv_dim]. Returns (y [B,1,d], state, conv_state)."""
    mixed, z, beta, g = _project(p, cfg, x)
    window = jnp.concatenate([conv_state, mixed.astype(conv_state.dtype)],
                             axis=1)                      # [B,K,C]
    conv = jnp.sum(window.astype(jnp.float32)
                   * p["conv"].astype(jnp.float32)[None], axis=1)
    q, k, v = _heads(cfg, jax.nn.silu(conv)[:, None])
    f32 = jnp.float32
    o, state = delta_rule_step(q[:, 0].astype(f32), k[:, 0].astype(f32),
                               v[:, 0].astype(f32), g[:, 0], beta[:, 0],
                               state)
    y = jnp.einsum("bsnh,nhd->bsd", _gated_norm(p, cfg, o[:, None], z),
                   p["wo"].astype(dt(cfg)))
    return y, state, window[:, 1:]


def gdn_rows(p, cfg: ModelConfig, x):
    """``gdn_fwd``'s output for a whole batch (the train step), rows in
    groups of ``ROW_GROUP``, each group rematerialised in the backward
    pass: the chunked rule's states and products are held for one group
    at a time. A batch that is not a multiple of ``ROW_GROUP`` is padded
    with zero rows, whose outputs are dropped."""
    B = x.shape[0]
    fwd = jax.checkpoint(lambda xg: gdn_fwd(p, cfg, xg)[0])
    pad = -B % ROW_GROUP
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    groups = x.reshape((-1, ROW_GROUP) + x.shape[1:])
    return jax.lax.map(fwd, groups).reshape(x.shape)[:B]
