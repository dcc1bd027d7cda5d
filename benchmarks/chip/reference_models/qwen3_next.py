"""Plain float32 reference of a Qwen3-Next decoder (Gated DeltaNet and
gated attention layers, sparse experts), at one chip's share of experts.

Written from the published architecture (Hugging Face
``modeling_qwen3_next.py`` and the model's ``config.json``). Per layer,
``x + mixer(norm(x))`` then ``x + moe(norm(x))``; layer ``i`` is full
attention where ``(i + 1) % full_attention_interval == 0`` and Gated
DeltaNet otherwise; a final norm and an untied LM head. Every RMSNorm of
the block, q/k-norm included, is zero-centred: ``norm(x) * (1 + w)``.

- Gated attention: q, a per-head output gate, k and v projections; q/k
  norm per head; rotate-half rotary embeddings on the first
  ``partial_rotary_factor * head_dim`` dims (frequencies over those dims,
  base ``rope_theta``); causal GQA softmax over ``q.k / sqrt(head_dim)``;
  the output times ``sigmoid(gate)`` before ``o_proj``.
- Gated DeltaNet: q, k (key heads), v, z (value heads), b and a
  projections; q, k, v through a causal depthwise conv (no bias) and
  SiLU; q and k L2-normalised per head (eps 1e-6), q scaled by
  1/sqrt(dk), both repeated to the value heads; ``beta = sigmoid(b)``,
  ``g = -exp(A_log) softplus(a + dt_bias)``; then token by token, in
  float32, ``S <- exp(g) S``, ``S <- S + k (beta (v - S^T k))^T``,
  ``o = S^T q``; a gated RMSNorm per head ``w norm(o) silu(z)`` and
  ``out_proj``.
- MoE: softmax over the router's experts, top ``num_experts_per_tok``,
  renormalised over them; of those, the experts held here (router ids
  ``expert_offset`` to ``expert_offset + num_experts``) each add their
  SwiGLU output times their weight; a shared SwiGLU expert is added times
  ``sigmoid(x . w_sg)``. The absent experts' part is left out, as on the
  chip.

Departures, none of them a change of the mathematics: Hugging Face keeps
q/gate, q/k/v/z and b/a in fused projections interleaved per key head;
here each is its own matrix (a permutation of the same weights). The
multi-token prediction module is not part of the decoder's forward pass
and is left out. Every matmul is one einsum in float32 at ``highest``
precision (``reference.mm``). To fit one chip, rows run one at a time
(each rematerialised in the backward pass) and each row's recurrence
keeps its state every ``REMAT`` tokens only.

Weights are made here from the seed by the seeding recipe the system's
initializer follows: one ``PRNGKey`` split into embedding, head and one
key per position of the four-layer period, folded with the period index
and split into mixer and MoE keys; each matrix ``normal * 1/sqrt(fan
in)`` (the embedding ``normal * 0.02``, the router ``normal * 0.02``,
the output projections ``1/sqrt(heads * head_dim)``, the conv taps
``1/sqrt(width)``), ``A_log = log(uniform(1, 16))``, ``dt_bias`` and the
gated norm's weight 1, the zero-centred norms' weights 0.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.reference import mm, rmsnorm, rope, seed_key

REMAT = 64
L2_EPS = 1e-6


def _normal(key, shape, scale):
    return jax.random.normal(key, shape) * scale


def _gdn_params(key, cfg: Dict) -> Dict:
    d = cfg["hidden_size"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kc = cfg["linear_conv_kernel_dim"]
    ks = jax.random.split(key, 9)
    s = 1 / math.sqrt(d)
    return {
        "wq": _normal(ks[0], (d, hk, dk), s),
        "wk": _normal(ks[1], (d, hk, dk), s),
        "wv": _normal(ks[2], (d, hv, dv), s),
        "wz": _normal(ks[3], (d, hv, dv), s),
        "wb": _normal(ks[4], (d, hv), s),
        "wa": _normal(ks[5], (d, hv), s),
        "conv": _normal(ks[6], (kc, 2 * hk * dk + hv * dv), 1 / math.sqrt(kc)),
        "A_log": jnp.log(jax.random.uniform(ks[7], (hv,), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": jnp.ones((hv,)),
        "norm": jnp.ones((dv,)),
        "wo": _normal(ks[8], (hv, dv, d), 1 / math.sqrt(hv * dv)),
    }


def _attn_params(key, cfg: Dict) -> Dict:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ks = jax.random.split(key, 5)
    s = 1 / math.sqrt(d)
    return {
        "wq": _normal(ks[0], (d, h, hd), s),
        "wk": _normal(ks[1], (d, kv, hd), s),
        "wv": _normal(ks[2], (d, kv, hd), s),
        "wo": _normal(ks[3], (h, hd, d), 1 / math.sqrt(h * hd)),
        "wg": _normal(ks[4], (d, h, hd), s),
        "q_norm": jnp.zeros((hd,)), "k_norm": jnp.zeros((hd,)),
    }


def _moe_params(key, cfg: Dict) -> Dict:
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    fs = cfg["shared_expert_intermediate_size"]
    e, r = cfg["num_experts"], cfg["deployment"]["router_experts"]
    ks = jax.random.split(key, 8)
    return {
        "router": _normal(ks[0], (d, r), 0.02),
        "w_gate": _normal(ks[1], (e, d, f), 1 / math.sqrt(d)),
        "w_up": _normal(ks[2], (e, d, f), 1 / math.sqrt(d)),
        "w_down": _normal(ks[3], (e, f, d), 1 / math.sqrt(f)),
        "shared_gate": _normal(ks[4], (d, fs), 1 / math.sqrt(d)),
        "shared_up": _normal(ks[5], (d, fs), 1 / math.sqrt(d)),
        "shared_down": _normal(ks[6], (fs, d), 1 / math.sqrt(fs)),
        "shared_expert_gate": _normal(ks[7], (d, 1), 1 / math.sqrt(d)),
    }


def _kinds(cfg: Dict):
    n = cfg["full_attention_interval"]
    return ["attn" if (i + 1) % n == 0 else "gdn" for i in range(n)]


def init_params(cfg: Dict, seed: int) -> Dict:
    """float32 weights from the seed, laid out as the system's: ``layers``
    holds one dict per position of the period (``{'gdn' | 'attn', 'moe',
    'norm1', 'norm2'}``), each leaf stacked over the periods."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    kinds = _kinds(cfg)
    periods = cfg["num_hidden_layers"] // len(kinds)
    keys = jax.random.split(seed_key(seed), 3 + len(kinds))
    layers = []
    for j, kind in enumerate(kinds):
        per = []
        for i in range(periods):
            k_mix, k_moe = jax.random.split(jax.random.fold_in(keys[3 + j], i))
            mix = (_attn_params if kind == "attn" else _gdn_params)(k_mix, cfg)
            per.append({kind: mix, "moe": _moe_params(k_moe, cfg),
                        "norm1": {"scale": jnp.zeros((d,))},
                        "norm2": {"scale": jnp.zeros((d,))}})
        layers.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per))
    return {"embed": {"tokens": _normal(keys[0], (v, d), 0.02)},
            "layers": tuple(layers),
            "final_norm": {"scale": jnp.zeros((d,))},
            "lm_head": {"w": _normal(keys[1], (d, v), 1 / math.sqrt(d))}}


def _zc_norm(x, w, eps):
    return rmsnorm(x, 1.0 + w, eps)


def _attention(cfg: Dict, a: Dict, y, lowp: bool):
    """Gated full attention over one row y [S, d]."""
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    rot = int(hd * cfg["partial_rotary_factor"])
    S = y.shape[0]
    q = _zc_norm(mm("sd,dnh->snh", y, a["wq"], lowp), a["q_norm"], eps)
    k = _zc_norm(mm("sd,dnh->snh", y, a["wk"], lowp), a["k_norm"], eps)
    v = mm("sd,dnh->snh", y, a["wv"], lowp)
    gate = mm("sd,dnh->snh", y, a["wg"], lowp)

    def partial_rope(t):
        return jnp.concatenate([rope(t[None, ..., :rot], theta)[0],
                                t[..., rot:]], -1)
    q, k = partial_rope(q), partial_rope(k)
    k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
    s = mm("qnh,knh->nqk", q, k, lowp) / math.sqrt(hd)
    causal = np.tril(np.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = mm("nqk,knh->qnh", p, v, lowp) * jax.nn.sigmoid(gate)
    return mm("snh,nhd->sd", o, a["wo"], lowp)


def _recurrence(q, k, v, g, beta):
    """The gated delta rule token by token over one row: q, k [S,H,dk],
    v [S,H,dv], g, beta [S,H]; the state is kept every ``REMAT`` tokens."""
    S, H, dk = k.shape
    dv = v.shape[-1]

    def step(state, xs):
        qt, kt, vt, gt, bt = xs
        state = state * jnp.exp(gt)[:, None, None]
        delta = bt[:, None] * (vt - jnp.sum(state * kt[..., None], axis=1))
        state = state + kt[..., None] * delta[:, None, :]
        return state, jnp.sum(state * qt[..., None], axis=1)

    c = min(REMAT, S)
    pad = -S % c
    xs = [jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1))
          for t in (q, k, v, g, beta)]
    xs = [t.reshape((-1, c) + t.shape[1:]) for t in xs]

    @jax.checkpoint
    def block(state, blk):
        return jax.lax.scan(step, state, blk)
    _, o = jax.lax.scan(block, jnp.zeros((H, dk, dv)), xs)
    return o.reshape(-1, H, dv)[:S]


def _gated_deltanet(cfg: Dict, p: Dict, y, lowp: bool):
    """Gated DeltaNet over one row y [S, d]."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kc = cfg["linear_conv_kernel_dim"]
    S = y.shape[0]
    q = mm("sd,dnh->snh", y, p["wq"], lowp).reshape(S, hk * dk)
    k = mm("sd,dnh->snh", y, p["wk"], lowp).reshape(S, hk * dk)
    v = mm("sd,dnh->snh", y, p["wv"], lowp).reshape(S, hv * dv)
    z = mm("sd,dnh->snh", y, p["wz"], lowp)
    beta = jax.nn.sigmoid(mm("sd,dn->sn", y, p["wb"], lowp))
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
        mm("sd,dn->sn", y, p["wa"], lowp) + p["dt_bias"])
    # causal depthwise conv: tap j meets the input j - (K-1) tokens back
    xc = jnp.pad(jnp.concatenate([q, k, v], -1), [(kc - 1, 0), (0, 0)])
    xc = jax.nn.silu(sum(xc[j:j + S] * p["conv"][j] for j in range(kc)))
    q = xc[:, :hk * dk].reshape(S, hk, dk)
    k = xc[:, hk * dk:2 * hk * dk].reshape(S, hk, dk)
    v = xc[:, 2 * hk * dk:].reshape(S, hv, dv)

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + L2_EPS)
    q, k = l2(q) / math.sqrt(dk), l2(k)
    q, k = jnp.repeat(q, hv // hk, axis=1), jnp.repeat(k, hv // hk, axis=1)
    o = _recurrence(q, k, v, g, beta)
    o = rmsnorm(o, p["norm"], cfg["rms_norm_eps"]) * jax.nn.silu(z)
    return mm("snh,nhd->sd", o, p["wo"], lowp)


def _moe(cfg: Dict, m: Dict, y, lowp: bool):
    """The held experts' part and the gated shared expert, one row."""
    e, top = cfg["num_experts"], cfg["num_experts_per_tok"]
    first = cfg["deployment"]["expert_offset"]
    probs = jax.nn.softmax(mm("sd,de->se", y, m["router"], lowp), -1)
    w, idx = jax.lax.top_k(probs, top)
    w = w / jnp.sum(w, -1, keepdims=True)
    # weight of each held expert: its renormalised weight where chosen
    held = jnp.sum(jnp.where(idx[..., None] == first + jnp.arange(e),
                             w[..., None], 0.0), axis=1)      # [S, e]
    gate = mm("sd,edf->sef", y, m["w_gate"], lowp)
    up = mm("sd,edf->sef", y, m["w_up"], lowp)
    out = mm("sef,efd->sed", jax.nn.silu(gate) * up, m["w_down"], lowp)
    shared = mm("sf,fd->sd",
                jax.nn.silu(mm("sd,df->sf", y, m["shared_gate"], lowp))
                * mm("sd,df->sf", y, m["shared_up"], lowp),
                m["shared_down"], lowp)
    sg = jax.nn.sigmoid(mm("sd,do->so", y, m["shared_expert_gate"], lowp))
    return jnp.sum(out * held[..., None], axis=1) + sg * shared


def logits(cfg: Dict, params: Dict, tokens, lowp: bool = False):
    """Teacher-forced logits [B, S, V] of ``tokens`` [B, S]."""
    kinds = _kinds(cfg)
    eps = cfg["rms_norm_eps"]

    @jax.checkpoint
    def row(toks):
        x = params["embed"]["tokens"][toks]
        for i in range(cfg["num_hidden_layers"]):
            j, period = i % len(kinds), i // len(kinds)
            lp = jax.tree.map(lambda w: w[period], params["layers"][j])
            y = _zc_norm(x, lp["norm1"]["scale"], eps)
            if kinds[j] == "attn":
                x = x + _attention(cfg, lp["attn"], y, lowp)
            else:
                x = x + _gated_deltanet(cfg, lp["gdn"], y, lowp)
            y = _zc_norm(x, lp["norm2"]["scale"], eps)
            x = x + _moe(cfg, lp["moe"], y, lowp)
        x = _zc_norm(x, params["final_norm"]["scale"], eps)
        return mm("sd,dv->sv", x, params["lm_head"]["w"], lowp)

    return jax.lax.map(row, tokens)
