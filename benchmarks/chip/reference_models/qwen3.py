"""Plain float32 reference of a dense Qwen3 decoder.

Written from the published architecture (Qwen3 technical report and the
Hugging Face ``Qwen3ForCausalLM`` config): token embedding, then per
layer ``x + attn(rmsnorm(x))`` and ``x + mlp(rmsnorm(x))``, a final
RMSNorm and an untied LM head. Attention is grouped-query attention with
RMSNorm over each head's q and k (qk-norm) before rotary embeddings
(half-split rotation, base ``rope_theta``), a causal softmax over
``q.k / sqrt(head_dim)``; the MLP is SwiGLU, ``down(silu(gate x) * up x)``.
No cache, no kernels, no batching tricks: every matmul is one einsum in
float32 at ``highest`` precision (``reference.mm``).

Weights are made here from the seed by the seeding recipe the system's
initializer follows (the split of one ``PRNGKey`` into embedding, head
and per-layer keys, each matrix ``normal * 1/sqrt(fan_in)``, the
embedding ``normal * 0.02``, norm scales 1), so both sides hold the same
numbers from the same seed without either handing the other anything.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.reference import mm, rmsnorm, rope, seed_key


def init_params(cfg: Dict, seed: int) -> Dict:
    """float32 weights from the seed, laid out as
    ``{'embed': {'tokens'}, 'layers': ({'attn', 'mlp', 'norm1', 'norm2'},),
    'final_norm', 'lm_head'}`` with each layer leaf stacked over a leading
    layer axis."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv, f = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["intermediate_size"])
    v, n_layers = cfg["vocab_size"], cfg["num_hidden_layers"]

    def normal(key, shape, scale):
        return jax.random.normal(key, shape) * scale

    keys = jax.random.split(seed_key(seed), 4)
    layers = []
    for i in range(n_layers):
        k_attn, k_mlp = jax.random.split(jax.random.fold_in(keys[3], i))
        ka = jax.random.split(k_attn, 4)
        km = jax.random.split(k_mlp, 3)
        layers.append({
            "attn": {
                "wq": normal(ka[0], (d, h, hd), 1 / math.sqrt(d)),
                "wk": normal(ka[1], (d, kv, hd), 1 / math.sqrt(d)),
                "wv": normal(ka[2], (d, kv, hd), 1 / math.sqrt(d)),
                "wo": normal(ka[3], (h, hd, d), 1 / math.sqrt(h * hd)),
                "q_norm": jnp.ones((hd,)), "k_norm": jnp.ones((hd,)),
            },
            "mlp": {
                "w_gate": normal(km[0], (d, f), 1 / math.sqrt(d)),
                "w_up": normal(km[1], (d, f), 1 / math.sqrt(d)),
                "w_down": normal(km[2], (f, d), 1 / math.sqrt(f)),
            },
            "norm1": {"scale": jnp.ones((d,))},
            "norm2": {"scale": jnp.ones((d,))},
        })
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return {"embed": {"tokens": normal(keys[0], (v, d), 0.02)},
            "layers": (stacked,),
            "final_norm": {"scale": jnp.ones((d,))},
            "lm_head": {"w": normal(keys[1], (d, v), 1 / math.sqrt(d))}}


def logits(cfg: Dict, params: Dict, tokens, lowp: bool = False):
    """Teacher-forced logits [B, S, V] of ``tokens`` [B, S]."""
    theta = float(cfg["rope_theta"])
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = cfg["rms_norm_eps"]
    x = params["embed"]["tokens"][tokens]
    S = tokens.shape[1]
    causal = np.tril(np.ones((S, S), bool))
    lp = params["layers"][0]
    for i in range(cfg["num_hidden_layers"]):
        a = jax.tree.map(lambda w: w[i], lp["attn"])
        m = jax.tree.map(lambda w: w[i], lp["mlp"])
        y = rmsnorm(x, lp["norm1"]["scale"][i], eps)
        q = rmsnorm(mm("bsd,dnh->bsnh", y, a["wq"], lowp), a["q_norm"], eps)
        k = rmsnorm(mm("bsd,dnh->bsnh", y, a["wk"], lowp), a["k_norm"], eps)
        v = mm("bsd,dnh->bsnh", y, a["wv"], lowp)
        q, k = rope(q, theta), rope(k, theta)
        # query head n reads key/value head n // (h / kv)
        k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
        s = mm("bqnh,bknh->bnqk", q, k, lowp) / math.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = mm("bnqk,bknh->bqnh", p, v, lowp)
        x = x + mm("bsnh,nhd->bsd", o, a["wo"], lowp)
        y = rmsnorm(x, lp["norm2"]["scale"][i], eps)
        g = mm("bsd,df->bsf", y, m["w_gate"], lowp)
        u = mm("bsd,df->bsf", y, m["w_up"], lowp)
        x = x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_down"], lowp)
    x = rmsnorm(x, params["final_norm"]["scale"], eps)
    return mm("bsd,dv->bsv", x, params["lm_head"]["w"], lowp)
