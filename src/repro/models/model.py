"""Unified decoder-only model builder covering all assigned families.

Depth is organized as ``num_periods`` repetitions of ``cfg.block_pattern``
(a tuple of (mixer, ffn) pairs). Parameters for each pattern position are
stacked over a leading ``num_periods`` axis and the forward pass scans over
periods (``scan_layers=True``, depth-independent HLO — required for the
80 dry-run compiles on one CPU) or unrolls them (``scan_layers=False``, used
by the roofline harness: XLA cost analysis counts a scan body only once, so
costs are extracted from unrolled depth-1/-2 builds and extrapolated).

Public entry points:
    init(key)                                   -> params
    forward(params, tokens, cond=None)          -> (logits, aux)   # train
    init_cache(batch, cache_len)                -> cache
    prefill(params, tokens, cache)              -> (logits, cache)
    decode_step(params, tokens, cache, positions) -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shd
from repro.models import gdn as G
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE
from repro.models import rwkv6 as R


class Model:
    def __init__(self, cfg: ModelConfig, *, scan_layers: bool = True,
                 remat: bool = True, window: Optional[int] = None):
        self.cfg = cfg
        self.scan_layers = scan_layers
        self.remat = remat
        # in unrolled (roofline cost) mode avoid inner scans: XLA cost
        # analysis counts while-loop bodies once (see hlo_costs.py)
        self.q_chunk = 512 if scan_layers else (1 << 30)
        self.mamba_chunk = 64 if scan_layers else (1 << 30)
        # attention window: explicit arg overrides config (long-context mode)
        self.window = window if window is not None else cfg.sliding_window

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------
    def _init_block(self, key, mixer: str, ffn: str) -> Dict:
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        if mixer == "attn":
            mix = L.init_attention(k1, cfg)
        elif mixer == "gdn":
            mix = G.init_gdn(k1, cfg)
        elif mixer == "mamba":
            mix = M.init_mamba(k1, cfg)
        elif mixer == "rwkv":
            mix = R.init_rwkv(k1, cfg)
        else:
            raise ValueError(mixer)
        ff = MOE.init_moe(k2, cfg) if ffn == "moe" else L.init_mlp(k2, cfg)
        zc = cfg.zero_centered_norm
        return {
            "norm1": L.init_rmsnorm(cfg.d_model, L.pdt(cfg), zc),
            "norm2": L.init_rmsnorm(cfg.d_model, L.pdt(cfg), zc),
            mixer: mix,
            ("moe" if ffn == "moe" else "mlp"): ff,
        }

    def init(self, key) -> Dict:
        cfg = self.cfg
        keys = jax.random.split(key, 3 + len(cfg.block_pattern))
        layers = []
        for p_idx, (mixer, ffn) in enumerate(cfg.block_pattern):
            per_period = [
                self._init_block(jax.random.fold_in(keys[3 + p_idx], i),
                                 mixer, ffn)
                for i in range(cfg.num_periods)
            ]
            layers.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_period))
        params = {
            "embed": {"tokens": L.dense_init(
                keys[0], (cfg.vocab_size, cfg.d_model), L.pdt(cfg),
                scale=0.02)},
            "layers": tuple(layers),
            "final_norm": L.init_rmsnorm(cfg.d_model, L.pdt(cfg),
                                         cfg.zero_centered_norm),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = {"w": L.dense_init(
                keys[1], (cfg.d_model, cfg.vocab_size), L.pdt(cfg))}
        return params

    def param_count(self, params) -> int:
        return sum(x.size for x in jax.tree.leaves(params))

    def _norm(self, p, x):
        cfg = self.cfg
        return L.rmsnorm(p, x, cfg.norm_eps, cfg.zero_centered_norm)

    def _moe_aux0(self):
        """The aux carry of the layer stack: MoE losses, and for an MoE
        stack its counters (assignments here, the busiest held expert)."""
        aux = {"lb_loss": jnp.zeros((), jnp.float32),
               "z_loss": jnp.zeros((), jnp.float32)}
        if self.cfg.uses_moe:
            aux.update(tokens_here=jnp.zeros((), jnp.int32),
                       load_max=jnp.zeros((), jnp.int32))
        return aux

    @staticmethod
    def _add_moe_aux(acc, aux):
        out = {"lb_loss": acc["lb_loss"] + aux["lb_loss"],
               "z_loss": acc["z_loss"] + aux["z_loss"]}
        if "tokens_here" in acc:
            out.update(tokens_here=acc["tokens_here"] + aux["tokens_here"],
                       load_max=jnp.maximum(acc["load_max"],
                                            aux["load_max"]))
        return out

    # ------------------------------------------------------------------
    # full-sequence forward (train / scoring)
    # ------------------------------------------------------------------
    def _block_fwd(self, bp: Dict, pattern: Tuple[str, str], x, positions,
                   aux_acc):
        cfg = self.cfg
        mixer, ffn = pattern
        h = self._norm(bp["norm1"], x)
        if mixer == "attn":
            with jax.named_scope("attn"):
                h = L.attention_fwd(bp["attn"], cfg, h, positions,
                                    window=self.window, q_chunk=self.q_chunk)
        elif mixer == "gdn":
            with jax.named_scope("gdn"):
                h = G.gdn_rows(bp["gdn"], cfg, h)
        elif mixer == "mamba":
            h = M.mamba_fwd(bp["mamba"], cfg, h, chunk=self.mamba_chunk)
        else:
            h = R.rwkv_fwd(bp["rwkv"], cfg, h)
        x = x + h
        h = self._norm(bp["norm2"], x)
        if ffn == "moe":
            with jax.named_scope("moe"):
                h, aux = MOE.moe_fwd(bp["moe"], cfg, h)
            aux_acc = self._add_moe_aux(aux_acc, aux)
        else:
            h = L.mlp_fwd(bp["mlp"], cfg, h)
        x = x + h
        x = shd(x, "batch", "seq", "act_embed")
        return x, aux_acc

    def _period_fwd(self, period_params, x, positions, aux_acc):
        # NOTE(hillclimb): nested per-block remat was tried for multi-block
        # patterns (jamba) and regressed temp memory 52->64 GiB on XLA:CPU
        # (the extra checkpoint boundaries defeat buffer reuse); disabled.
        nested = False
        for p_idx, pattern in enumerate(self.cfg.block_pattern):
            fwd = functools.partial(self._block_fwd, pattern=pattern)
            if nested:
                fwd = jax.checkpoint(fwd, static_argnums=())
            x, aux_acc = fwd(period_params[p_idx], x=x, positions=positions,
                             aux_acc=aux_acc)
        return x, aux_acc

    def forward(self, params, tokens, cond=None, positions=None):
        """tokens: [B,S] int32; cond: [B,Lc,d_model] early-fusion embeddings.

        Returns (logits [B,S,V] fp32, aux dict with MoE losses).
        """
        cfg = self.cfg
        x, aux = self._backbone(params, tokens, cond, positions)
        x = self._norm(params["final_norm"], x)
        w_out = (params["embed"]["tokens"].T if cfg.tie_embeddings
                 else params["lm_head"]["w"])
        w_out = shd(w_out.astype(L.dt(cfg)), None, "vocab")  # PERF(iter 1)
        logits = jnp.einsum("bsd,dv->bsv", x, w_out)
        logits = shd(logits, "batch", "seq", "vocab")
        return logits, aux

    def forward_logprobs(self, params, tokens, cond=None, chunk: int = 512):
        """Fused, seq-chunked head: returns (logprobs [B,S-1] fp32, aux)
        without ever materializing [B,S,V] logits — the head matmul, the
        logsumexp, and the label pick run per sequence chunk under remat.
        This is what the GRPO train_step uses; ``forward`` keeps the plain
        logits path for sampling/scoring of short sequences.
        """
        cfg = self.cfg
        B, S = tokens.shape
        x, aux = self._backbone(params, tokens, cond)
        x = self._norm(params["final_norm"], x)
        x = shd(x, "batch", "seq", "act_embed")
        w_out = (params["embed"]["tokens"].T if cfg.tie_embeddings
                 else params["lm_head"]["w"]).astype(L.dt(cfg))
        # PERF(iter 1): contract an UNSHARDED d — gather the (data,model)-
        # sharded head weight over "data" (tens of MB) rather than letting
        # GSPMD all-reduce [B,chunk,V] partial sums per chunk (GBs).
        w_out = shd(w_out, None, "vocab")
        labels = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], axis=1)

        if not self.scan_layers:       # roofline cost mode: no inner scans
            chunk = S
        c = min(chunk, S)
        while S % c:
            c -= 1
        nb = S // c

        def body(carry, xs):
            xc, labc = xs                                  # [B,c,d], [B,c]
            logits = jnp.einsum("bcd,dv->bcv", xc, w_out)  # bf16 [B,c,V]
            m = jax.lax.stop_gradient(jnp.max(logits, -1, keepdims=True))
            shifted = (logits - m).astype(jnp.float32)
            lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
            iota = jax.lax.broadcasted_iota(labc.dtype,
                                            (1, 1, logits.shape[-1]), 2)
            lab = jnp.sum(jnp.where(labc[..., None] == iota, shifted, 0.0),
                          axis=-1)
            return carry, lab - lse

        if nb == 1:
            _, lp = body(None, (x, labels))
        else:
            xs = (jnp.moveaxis(x.reshape(B, nb, c, -1), 1, 0),
                  jnp.moveaxis(labels.reshape(B, nb, c), 1, 0))
            _, lp = jax.lax.scan(jax.checkpoint(body), None, xs)
            lp = jnp.moveaxis(lp, 0, 1).reshape(B, S)
        return lp[:, :-1], aux

    def _embed(self, params, tokens):
        """Token embedding. Under SPMD, a one-hot matmul (MaxText-style): the
        gather's backward is a scatter-add into the full [V,d] table that
        GSPMD cannot shard (measured 2 GiB/device f32 replicated on
        chameleon-34b); as a matmul, dW shards like the table itself."""
        from repro.distributed.sharding import sharding_active
        cfg = self.cfg
        table = params["embed"]["tokens"].astype(L.dt(cfg))
        if not sharding_active():
            return jnp.take(table, tokens, axis=0)
        onehot = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=L.dt(cfg))
        onehot = shd(onehot, "batch", "seq", "vocab")
        table = shd(table, "vocab", None)                   # PERF(iter 1)
        return jnp.einsum("bsv,vd->bsd", onehot, table)

    def _backbone(self, params, tokens, cond=None, positions=None):
        """Shared embed + layer stack; returns (x [B,S,d] pre-final-norm
        residual output, aux)."""
        cfg = self.cfg
        B, S = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        x = self._embed(params, tokens)
        if cond is not None:
            lc = cond.shape[1]
            x = jnp.concatenate([cond.astype(x.dtype), x[:, lc:, :]], axis=1)
        x = shd(x, "batch", "seq", "act_embed")
        aux = self._moe_aux0()
        if self.scan_layers:
            def body(carry, period_params):
                x, aux = carry
                x, aux = self._period_fwd(period_params, x, positions, aux)
                return (x, aux), ()
            if self.remat:
                body = jax.checkpoint(body)
            (x, aux), _ = jax.lax.scan(body, (x, aux), params["layers"])
        else:
            for i in range(cfg.num_periods):
                pp = jax.tree.map(lambda a: a[i], params["layers"])
                fwd = self._period_fwd
                if self.remat:
                    fwd = jax.checkpoint(fwd)
                x, aux = fwd(pp, x, positions, aux)
        return x, aux

    # ------------------------------------------------------------------
    # caches
    # ------------------------------------------------------------------
    def _block_cache_spec(self, pattern, batch: int, cache_len: int):
        cfg = self.cfg
        mixer, _ = pattern
        np_ = cfg.num_periods
        if mixer == "attn":
            clen = min(cache_len, self.window) if self.window else cache_len
            shape = (np_, batch, cfg.num_kv_heads, clen, cfg.head_dim)
            return {"k": jnp.zeros(shape, L.dt(cfg)),
                    "v": jnp.zeros(shape, L.dt(cfg))}
        if mixer == "gdn":
            return {"S": jnp.zeros((np_, batch, cfg.linear_num_value_heads,
                                    cfg.linear_key_head_dim,
                                    cfg.linear_value_head_dim), jnp.float32),
                    "conv": jnp.zeros((np_, batch,
                                       cfg.linear_conv_kernel_dim - 1,
                                       cfg.gdn_conv_dim), L.dt(cfg))}
        if mixer == "mamba":
            return {"h": jnp.zeros((np_, batch, cfg.mamba_d_inner,
                                    cfg.mamba_d_state), jnp.float32),
                    "conv": jnp.zeros((np_, batch, cfg.mamba_d_conv - 1,
                                       cfg.mamba_d_inner), L.dt(cfg))}
        if mixer == "rwkv":
            return {"prev_x": jnp.zeros((np_, batch, cfg.d_model), L.dt(cfg)),
                    "S": jnp.zeros((np_, batch, cfg.num_rwkv_heads,
                                    cfg.rwkv_head_dim, cfg.rwkv_head_dim),
                                   jnp.float32)}
        raise ValueError(mixer)

    def init_cache(self, batch: int, cache_len: int):
        return tuple(self._block_cache_spec(pat, batch, cache_len)
                     for pat in self.cfg.block_pattern)

    # ------------------------------------------------------------------
    # paged KV pool (attention stacks only; see rl/paged_kv.py)
    # ------------------------------------------------------------------
    def supports_paged(self) -> bool:
        """Paged KV needs position-addressable per-token state: every
        mixer must be attention (a recurrent mamba/rwkv state has no
        page structure) and no ring-buffered sliding window (a page
        holds absolute positions, a ring holds positions mod window)."""
        return (self.window is None
                and all(m == "attn" for m, _ in self.cfg.block_pattern))

    def init_paged_pool(self, num_rows: int, page_size: int):
        """Zeroed page pool, one leaf pair per block-pattern position:
        ``[num_periods, num_rows, kvH, page_size, hd]``. ``num_rows``
        includes the engine's trash row (id ``num_rows-1``), which
        absorbs padded-table writes and gathers."""
        if not self.supports_paged():
            raise ValueError(
                f"{self.cfg.name}: paged KV requires an attention-only "
                "stack with no sliding window")
        cfg = self.cfg
        shape = (cfg.num_periods, num_rows, cfg.num_kv_heads, page_size,
                 cfg.head_dim)
        return tuple({"k": jnp.zeros(shape, L.dt(cfg)),
                      "v": jnp.zeros(shape, L.dt(cfg))}
                     for _ in cfg.block_pattern)

    # logical axes per cache leaf, aligned with _block_cache_spec shapes.
    # Under SERVE_RULES the attention cache shards its sequence dim over
    # the group's "model" axis ("cache_seq" rule) — the layout the §6.3
    # decode path wants, since each decode step touches one position of
    # every head but streams the whole context.
    _CACHE_AXES = {
        "attn": {"k": (None, None, "cache_kv_heads", "cache_seq", None),
                 "v": (None, None, "cache_kv_heads", "cache_seq", None)},
        "gdn": {"S": (None, None, None, None, None),
                "conv": (None, None, None, None)},
        "mamba": {"h": (None, None, "mamba_inner", "ssm_state"),
                  "conv": (None, None, None, "mamba_inner")},
        "rwkv": {"prev_x": (None, None, None),
                 "S": (None, None, "rwkv_heads", None, None)},
    }

    # paged pool leaves are [num_periods, num_rows, kvH, page, hd]: the
    # within-page position dim shards over the group ("cache_page_seq"),
    # the page-granular analogue of the dense "cache_seq" layout
    _PAGED_CACHE_AXES = {
        "attn": {"k": (None, None, "cache_kv_heads", "cache_page_seq", None),
                 "v": (None, None, "cache_kv_heads", "cache_page_seq", None)},
    }

    def cache_logical_axes(self):
        """Pytree matching ``init_cache`` structure whose leaves are the
        logical-axis tuples of each cache leaf."""
        return tuple(dict(self._CACHE_AXES[mixer])
                     for mixer, _ in self.cfg.block_pattern)

    def paged_cache_logical_axes(self):
        return tuple(dict(self._PAGED_CACHE_AXES[mixer])
                     for mixer, _ in self.cfg.block_pattern)

    def cache_sharding(self, cache, mesh, rules, axes=None):
        """NamedSharding pytree for an engine cache on ``mesh`` under a
        logical rule set (divisibility handled exactly like params, via
        ``fit_spec``). ``axes`` selects the layout — dense
        (``cache_logical_axes``, default) or paged
        (``paged_cache_logical_axes``)."""
        from jax.sharding import NamedSharding
        from repro.distributed.sharding import fit_spec, resolve_spec

        def one(leaf, leaf_axes):
            spec = fit_spec(leaf.shape,
                            resolve_spec(leaf_axes, rules, mesh), mesh)
            return NamedSharding(mesh, spec)
        # tree.map flattens up to the CACHE's leaves (arrays), so the
        # logical-axis tuples sitting at those positions pass through
        # whole instead of being descended into
        return jax.tree.map(one, cache,
                            axes if axes is not None
                            else self.cache_logical_axes())

    # ------------------------------------------------------------------
    # KV-cache slot migration (live prefill/decode disaggregation)
    # ------------------------------------------------------------------
    # Every cache leaf is laid out (num_periods, batch, ...), so one
    # trajectory's state is the batch-axis slice at its slot index. These
    # two helpers are the data-plane handoff used by the PD-disaggregated
    # engines: the prefill engine extracts a freshly filled slot and the
    # decode engine injects it into one of its free slots.

    def extract_cache_slot(self, cache, slot: int):
        """Slice one slot (batch axis == 1) out of an engine cache pytree.

        Returns a cache pytree with batch dimension 1, suitable for
        ``inject_cache_slot`` on another engine built from the same model
        with the same ``cache_len``.
        """
        return jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1),
            cache)

    def inject_cache_slot(self, cache, slot_cache, slot: int):
        """Write a batch-1 cache pytree into ``slot`` of a full cache."""
        return jax.tree.map(
            lambda big, little: jax.lax.dynamic_update_slice_in_dim(
                big, little.astype(big.dtype), slot, axis=1),
            cache, slot_cache)

    def paged_to_dense_slot(self, pool, table):
        """Gather one slot's pages into the batch-1 DENSE cache layout
        (``init_cache(1, P*page)`` shapes) — the portable KVHandoff
        format. ``table``: [P] int32 page ids, padded with the trash row
        past the slot's allocation (those positions carry junk the
        consumer masks by position, exactly like a dense engine's stale
        rows). Eager ops, like ``extract_cache_slot``."""
        table = jnp.asarray(table, jnp.int32)

        def one(leaf):
            g = jnp.swapaxes(leaf[:, table], 1, 2)   # [np,kvH,P,page,hd]
            np_, kvh, P, page, hd = g.shape
            return g.reshape(np_, kvh, P * page, hd)[:, None]
        return jax.tree.map(one, pool)

    def dense_slot_to_pages(self, pool, slot_cache, table):
        """Scatter a batch-1 dense cache pytree into a slot's pages (the
        inject half of a PD handoff / FT restore into a paged engine).
        Positions past the allocation land in the trash row."""
        table = jnp.asarray(table, jnp.int32)

        def one(leaf, dense):
            np_, _, kvh, length, hd = dense.shape
            P = table.shape[0]
            pages = dense[:, 0].reshape(np_, kvh, P, length // P, hd)
            pages = jnp.swapaxes(pages, 1, 2)        # [np,P,kvH,page,hd]
            return leaf.at[:, table].set(pages.astype(leaf.dtype))
        return jax.tree.map(one, pool, slot_cache)

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def _block_decode(self, bp, pattern, x, cache, positions, live=None):
        """One decode block; returns (x, new cache, MoE counters or None).
        ``live`` [B] marks the rows still emitting: only they are routed
        to the experts."""
        cfg = self.cfg
        mixer, ffn = pattern
        h = self._norm(bp["norm1"], x)
        if mixer == "attn":
            with jax.named_scope("attn"):
                h, k_c, v_c = L.attention_decode(
                    bp["attn"], cfg, h, cache["k"], cache["v"], positions,
                    lengths=positions, window=self.window)
            new_cache = {"k": k_c, "v": v_c}
        elif mixer == "gdn":
            with jax.named_scope("gdn"):
                h, S, conv = G.gdn_decode(bp["gdn"], cfg, h, cache["S"],
                                          cache["conv"])
            new_cache = {"S": S, "conv": conv}
        elif mixer == "mamba":
            h, h_state, conv = M.mamba_decode(bp["mamba"], cfg, h,
                                              cache["h"], cache["conv"])
            new_cache = {"h": h_state, "conv": conv}
        else:
            h, prev_x, S = R.rwkv_decode(bp["rwkv"], cfg, h,
                                         cache["prev_x"], cache["S"])
            new_cache = {"prev_x": prev_x, "S": S}
        x = x + h
        h = self._norm(bp["norm2"], x)
        stats = None
        if ffn == "moe":
            with jax.named_scope("moe"):
                h, aux = MOE.moe_fwd(
                    bp["moe"], cfg, h,
                    live=None if live is None else live[:, None])
            stats = {"touched": aux["touched"],
                     "tokens_here": aux["tokens_here"]}
        else:
            h = L.mlp_fwd(bp["mlp"], cfg, h)
        return x + h, new_cache, stats

    def _block_decode_paged(self, bp, pattern, x, pool_leaf, tables,
                            positions, page_size):
        cfg = self.cfg
        mixer, ffn = pattern
        if mixer != "attn":
            raise ValueError(f"paged decode: unsupported mixer {mixer!r}")
        h = self._norm(bp["norm1"], x)
        h, k_p, v_p = L.attention_decode_paged(
            bp["attn"], cfg, h, pool_leaf["k"], pool_leaf["v"], tables,
            positions, page_size)
        x = x + h
        h = self._norm(bp["norm2"], x)
        if ffn == "moe":
            h, _ = MOE.moe_fwd(bp["moe"], cfg, h)
        else:
            h = L.mlp_fwd(bp["mlp"], cfg, h)
        return x + h, {"k": k_p, "v": v_p}

    def decode_step_paged(self, params, tokens, pool, tables, positions,
                          page_size: int):
        """Paged analogue of :meth:`decode_step`. ``pool`` leaves are
        ``[num_periods, num_rows, kvH, page, hd]``; ``tables``: [B,P]
        page ids (trash-padded); B is the COMPACTED active batch, not
        max_slots. Per-row math is bit-identical to the dense step (see
        ``attention_decode_paged``)."""
        cfg = self.cfg
        x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
        x = x.astype(L.dt(cfg))
        x = shd(x, "batch", "seq", "act_embed")

        if self.scan_layers:
            def body(x, xs):
                period_params, period_pool = xs
                new_pool = []
                for p_idx, pat in enumerate(self.cfg.block_pattern):
                    x, nc = self._block_decode_paged(
                        period_params[p_idx], pat, x, period_pool[p_idx],
                        tables, positions, page_size)
                    new_pool.append(nc)
                return x, tuple(new_pool)
            x, new_pool = jax.lax.scan(body, x, (params["layers"], pool))
        else:
            outs = []
            for i in range(cfg.num_periods):
                pp = jax.tree.map(lambda a: a[i], params["layers"])
                pc = jax.tree.map(lambda a: a[i], pool)
                ncs = []
                for p_idx, pat in enumerate(cfg.block_pattern):
                    x, nc = self._block_decode_paged(
                        pp[p_idx], pat, x, pc[p_idx], tables, positions,
                        page_size)
                    ncs.append(nc)
                outs.append(tuple(ncs))
            new_pool = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)

        x = self._norm(params["final_norm"], x)
        w_out = (params["embed"]["tokens"].T if cfg.tie_embeddings
                 else params["lm_head"]["w"])
        logits = jnp.einsum("bsd,dv->bsv", x, w_out.astype(L.dt(cfg)))
        return logits[:, 0].astype(jnp.float32), new_pool

    def gather_paged_cache(self, pool, tables):
        """Gather each compacted row's page table out of the pool into
        the dense block-cache layout (``[np, B, kvH, P*page, hd]`` per
        leaf, i.e. ``init_cache(B, P*page)`` shapes). ``tables``: [B,P]
        int32, trash-padded — padded positions carry junk that downstream
        attention masks by position, exactly like a dense engine's stale
        rows."""
        def one(leaf):
            g = jnp.swapaxes(leaf[:, tables], 2, 3)  # [np,B,kvH,P,page,hd]
            np_, b, kvh, p, page, hd = g.shape
            return g.reshape(np_, b, kvh, p * page, hd)
        return jax.tree.map(one, pool)

    def scatter_block_writes(self, pool, cache, tables, positions,
                             k_steps: int, page_size: int):
        """Write the pages a K-step decode block can have touched back
        into the pool. A block starting at ``positions[b]`` writes the
        span ``[pos, pos+K)``, which lands on at most
        ``(K-1)//page + 2`` pages starting at ``pos // page``; everything
        else in the gathered view is byte-identical to the pool already,
        so rewriting a partially-touched page is idempotent. Page-id
        clamping to the last table column mirrors ``dynamic_slice``'s
        automatic start clamping, so an overshooting candidate rewrites
        the final page (or the trash row) with its own bytes."""
        n_rows, n_pages = tables.shape
        n_cand = (k_steps - 1) // page_size + 2

        def one(leaf, dense):
            np_, _, kvh, _, hd = dense.shape
            for b in range(n_rows):
                first = positions[b] // page_size
                for t in range(n_cand):
                    j = first + t
                    pid = tables[b, jnp.minimum(j, n_pages - 1)]
                    piece = jax.lax.dynamic_slice(
                        dense, (0, b, 0, j * page_size, 0),
                        (np_, 1, kvh, page_size, hd))
                    leaf = jax.lax.dynamic_update_slice(
                        leaf, piece.astype(leaf.dtype), (0, pid, 0, 0, 0))
            return leaf
        return jax.tree.map(one, pool, cache)

    def decode_block_paged(self, params, tokens, pool, tables, positions,
                           keys, temperatures, stop_ids, budgets, sample_fn,
                           page_size: int):
        """K paged decode steps in one compiled call. Rather than carry
        the pool through the scan (a per-step pool scatter is ~100x the
        cost of the gather on XLA:CPU), the block gathers each row's
        pages into a dense cache ONCE, runs the unmodified dense
        :meth:`decode_block` on it — bit-identical per-row math, which is
        what keeps paged greedy output byte-equal to the dense engine —
        and writes only the touched pages back at the end. ``tables`` is
        loop-invariant: every page a slot can touch is allocated at
        admission."""
        cache = self.gather_paged_cache(pool, tables)
        toks, lps, emitted, cache, stats = self.decode_block(
            params, tokens, cache, positions, keys, temperatures,
            stop_ids, budgets, sample_fn)
        pool = self.scatter_block_writes(pool, cache, tables, positions,
                                         keys.shape[0], page_size)
        return toks, lps, emitted, pool, stats

    def decode_block(self, params, tokens, cache, positions, keys,
                     temperatures, stop_ids, budgets, sample_fn):
        """K decode steps in one compiled call (``jax.lax.scan`` over the
        stacked ``keys``): the device-resident decode loop. Host dispatch,
        per-step Python overhead, and the token round-trip are amortized
        K-fold; stop-token / length / already-finished masking happens on
        device so a slot that finishes mid-block freezes (its token and
        position stop advancing, making the remaining cache writes
        idempotent re-writes of the same entry) without a host round-trip.

        tokens: [B,1] last emitted token per slot; positions: [B] absolute
        position of that token; keys: [K, ...] stacked PRNG keys, one per
        inner step (same one-key-per-decode-step schedule as K single-step
        dispatches, so sampled streams are reproducible across block
        sizes); temperatures: [B]; stop_ids: [B,W] per-slot stop tokens
        padded with -1; budgets: [B] int32 tokens each slot may still emit
        (0 = frozen — inactive slots ride along exactly like the
        single-step path's zero rows). ``sample_fn(key, logits,
        temperatures) -> (toks [B], lps [B])`` runs inside the scanned
        body (see ``repro.rl.sampling.sample_mixed``).

        Returns (toks [K,B], lps [K,B], emitted [K,B] bool, cache, stats).
        Each slot's emitted column is a True-prefix: host code appends
        exactly the emitted tokens and re-derives stop/length finishing
        from them. ``stats`` is empty for a stack without experts; for an
        MoE stack it holds, summed over the K steps and the layers,
        ``touched`` (held experts some emitting row routed to) and
        ``tokens_here`` (the emitting rows' assignments to held experts).
        """
        moe = self.cfg.uses_moe

        def body(carry, key):
            tok, pos, rem, done, cache = carry
            logits, cache, stats = self._decode_step(
                params, tok, cache, pos, ~done if moe else None)
            t, lp = sample_fn(key, logits, temperatures)
            emit = ~done
            # frozen rows re-feed their previous token at the same
            # position: the attention cache write is idempotent and a
            # recurrent state only advances in a slot that is finished
            # (and therefore re-prefilled before reuse)
            t = jnp.where(emit, t, tok[:, 0])
            lp = jnp.where(emit, lp, 0.0)
            rem = rem - emit.astype(rem.dtype)
            hit_stop = jnp.any(t[:, None] == stop_ids, axis=1)
            done = done | (emit & hit_stop) | (rem <= 0)
            pos = pos + emit.astype(pos.dtype)
            return (t[:, None], pos, rem, done, cache), (t, lp, emit, stats)

        carry0 = (tokens, positions, budgets, budgets <= 0, cache)
        (_, _, _, _, cache), (toks, lps, emitted, stats) = jax.lax.scan(
            body, carry0, keys)
        return toks, lps, emitted, cache, jax.tree.map(
            lambda a: a.sum(0), stats)

    def decode_step(self, params, tokens, cache, positions):
        """tokens: [B,1] int32; positions: [B] int32 (absolute positions).

        Returns (logits [B,V] fp32, new cache).
        """
        logits, cache, _ = self._decode_step(params, tokens, cache,
                                             positions)
        return logits, cache

    def _decode_step(self, params, tokens, cache, positions, live=None):
        """:meth:`decode_step`, also returning the MoE counters of the
        step summed over layers (an empty dict for a stack without
        experts); ``live`` [B] as in :meth:`_block_decode`."""
        cfg = self.cfg
        x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
        x = x.astype(L.dt(cfg))
        x = shd(x, "batch", "seq", "act_embed")

        def add(acc, st):
            return acc if st is None else jax.tree.map(jnp.add, acc, st)
        stats0 = ({"touched": jnp.zeros((), jnp.int32),
                   "tokens_here": jnp.zeros((), jnp.int32)}
                  if cfg.uses_moe else {})

        if self.scan_layers:
            def body(carry, xs):
                x, stats = carry
                period_params, period_cache = xs
                new_caches = []
                for p_idx, pat in enumerate(self.cfg.block_pattern):
                    x, nc, st = self._block_decode(
                        period_params[p_idx], pat, x, period_cache[p_idx],
                        positions, live)
                    stats = add(stats, st)
                    new_caches.append(nc)
                return (x, stats), tuple(new_caches)
            (x, stats), new_cache = jax.lax.scan(
                body, (x, stats0), (params["layers"], cache))
        else:
            new_caches = []
            stats = stats0
            for i in range(cfg.num_periods):
                pp = jax.tree.map(lambda a: a[i], params["layers"])
                pc = jax.tree.map(lambda a: a[i], cache)
                ncs = []
                for p_idx, pat in enumerate(cfg.block_pattern):
                    x, nc, st = self._block_decode(pp[p_idx], pat, x,
                                                   pc[p_idx], positions,
                                                   live)
                    stats = add(stats, st)
                    ncs.append(nc)
                new_caches.append(tuple(ncs))
            new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *new_caches)

        x = self._norm(params["final_norm"], x)
        w_out = (params["embed"]["tokens"].T if cfg.tie_embeddings
                 else params["lm_head"]["w"])
        logits = jnp.einsum("bsd,dv->bsv", x, w_out.astype(L.dt(cfg)))
        return logits[:, 0].astype(jnp.float32), new_cache, stats

    # ------------------------------------------------------------------
    # prefill (fills KV/state caches, returns last-token logits)
    # ------------------------------------------------------------------
    def prefill(self, params, tokens, cache, cond=None, last_pos=None,
                slot=None):
        """tokens: [B,S]. Fills cache positions [0,S) and returns
        (logits [B,V] at position ``last_pos`` (default S-1), cache).

        With ``slot`` given (int or traced scalar), ``tokens`` is batch-1
        and ``cache`` is a FULL engine cache (leaves laid out
        ``[num_periods, max_slots, ...]``): the prompt's cache entries are
        written directly into that slot's batch row via
        ``dynamic_update_slice``, so admission prefill needs no transient
        batch-1 cache and — with the cache argument donated at the jit
        boundary — no full-cache copy either. Without ``slot`` the batch
        rows of ``tokens`` and ``cache`` correspond 1:1 (legacy mode,
        requires ``B == cache batch``).
        """
        cfg = self.cfg
        B, S = tokens.shape
        slot0 = 0 if slot is None else slot
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        # a bucket-padded prompt: positions past last_pos are masked out of
        # recurrent state and expert routing (attention masks them causally)
        valid = (None if last_pos is None
                 else positions <= last_pos[:, None])

        def to_slot(big, little):
            if slot is None:
                return little.astype(big.dtype)
            return jax.lax.dynamic_update_slice_in_dim(
                big, little.astype(big.dtype), slot, axis=0)
        x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
        x = x.astype(L.dt(cfg))
        if cond is not None:
            lc = cond.shape[1]
            x = jnp.concatenate([cond.astype(x.dtype), x[:, lc:, :]], axis=1)
        x = shd(x, "batch", "seq", "act_embed")

        def period_prefill(period_params, period_cache, x):
            new_caches = []
            for p_idx, pat in enumerate(cfg.block_pattern):
                bp = period_params[p_idx]
                mixer, ffn = pat
                h = self._norm(bp["norm1"], x)
                if mixer == "attn":
                    with jax.named_scope("attn"):
                        cdt = L.dt(cfg)
                        q, k, v = L._qkv(bp["attn"], cfg, h, positions)
                        ccache = period_cache[p_idx]
                        clen = ccache["k"].shape[2]
                        kw = k[:, :, -clen:, :] if clen < S else k
                        vw = v[:, :, -clen:, :] if clen < S else v
                        if self.window is not None and clen == self.window:
                            # ring layout: token t lives in slot t % window
                            sl = (jnp.arange(max(S - clen, 0), S) % clen)
                            if slot is None:
                                k_c = ccache["k"].at[:, :, sl, :].set(
                                    kw.astype(cdt))
                                v_c = ccache["v"].at[:, :, sl, :].set(
                                    vw.astype(cdt))
                            else:
                                # slice the slot's batch row out first: mixing
                                # the scalar `slot` with the advanced index
                                # `sl` in one .at[] would move the advanced
                                # dims to the front (transposed write)
                                def ring_write(big, little):
                                    row = jax.lax.dynamic_slice_in_dim(
                                        big, slot, 1, axis=0)
                                    row = row.at[:, :, sl, :].set(
                                        little.astype(cdt))
                                    return jax.lax.dynamic_update_slice_in_dim(
                                        big, row, slot, axis=0)
                                k_c = ring_write(ccache["k"], kw)
                                v_c = ring_write(ccache["v"], vw)
                        else:
                            k_c = jax.lax.dynamic_update_slice(
                                ccache["k"], kw.astype(cdt), (slot0, 0, 0, 0))
                            v_c = jax.lax.dynamic_update_slice(
                                ccache["v"], vw.astype(cdt), (slot0, 0, 0, 0))
                        out = L._attend_causal(q, k, v, cfg, self.window,
                                               q_chunk=self.q_chunk)
                        h = L.attention_out(bp["attn"], cfg, h, out)
                        nc = {"k": k_c, "v": v_c}
                elif mixer == "gdn":
                    with jax.named_scope("gdn"):
                        h, st = G.gdn_fwd(bp["gdn"], cfg, h, valid=valid,
                                          last_pos=last_pos)
                    nc = jax.tree.map(to_slot, period_cache[p_idx], st)
                elif mixer == "mamba":
                    h, h_state, conv = M.mamba_fwd(
                        bp["mamba"], cfg, h, return_state=True,
                        chunk=self.mamba_chunk)
                    nc = jax.tree.map(to_slot, period_cache[p_idx],
                                      {"h": h_state, "conv": conv})
                else:
                    h, prev_x, S_out = R.rwkv_fwd(bp["rwkv"], cfg, h,
                                                  return_state=True)
                    nc = jax.tree.map(to_slot, period_cache[p_idx],
                                      {"prev_x": prev_x, "S": S_out})
                x = x + h
                h = self._norm(bp["norm2"], x)
                if ffn == "moe":
                    with jax.named_scope("moe"):
                        h, _ = MOE.moe_fwd(bp["moe"], cfg, h, live=valid)
                else:
                    h = L.mlp_fwd(bp["mlp"], cfg, h)
                x = x + h
                new_caches.append(nc)
            return x, tuple(new_caches)

        if self.scan_layers:
            def body(x, xs):
                period_params, period_cache = xs
                x, ncs = period_prefill(period_params, period_cache, x)
                return x, ncs
            x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
        else:
            outs = []
            for i in range(cfg.num_periods):
                pp = jax.tree.map(lambda a: a[i], params["layers"])
                pc = jax.tree.map(lambda a: a[i], cache)
                x, ncs = period_prefill(pp, pc, x)
                outs.append(ncs)
            new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)

        x = self._norm(params["final_norm"], x)
        if last_pos is None:
            x_last = x[:, -1, :]
        else:
            x_last = jnp.take_along_axis(
                x, last_pos[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        w_out = (params["embed"]["tokens"].T if cfg.tie_embeddings
                 else params["lm_head"]["w"])
        logits = jnp.einsum("bd,dv->bv", x_last, w_out.astype(L.dt(cfg)))
        return logits.astype(jnp.float32), new_cache

    def prefill_paged(self, params, tokens, pool, table, page_size: int,
                      last_pos=None, ctx_len=None):
        """Prefill a (tail of a) prompt into a slot's KV pages.

        tokens: [1, S] with S a page multiple (engine pads); table: [P]
        int32 page ids for the WHOLE slot, trash-padded past the
        allocation; last_pos: [1] index of the last real prompt token
        WITHIN ``tokens``.

        Two modes, selected statically so each gets its own compile:

        - ``ctx_len=None`` (fresh prompt, no prefix hit): positions start
          at 0 and attention runs ``_attend_causal`` over the tail alone —
          the exact op sequence of the dense :meth:`prefill`, so the tail
          logits (and the K/V bytes written to the pages) are bitwise
          identical to the dense engine's.
        - ``ctx_len`` a traced int32 scalar (prefix fork): ``ctx_len``
          cached prefix tokens (a page multiple) already sit in the
          slot's leading pages; the tail is written at positions
          ``ctx_len + [0, S)`` and attends over the full gathered table
          (cached prefix + its own causal tail, everything else masked
          to exact zeros).

        Returns (logits [1,V] fp32 at ``last_pos``, pool).
        """
        cfg = self.cfg
        if not self.supports_paged():
            raise ValueError(f"{cfg.name}: paged prefill needs an "
                             "attention-only, non-windowed stack")
        B, S = tokens.shape
        n_tail_pages = S // page_size
        P = table.shape[0]
        base = jnp.arange(S)[None, :]
        positions = base if ctx_len is None else base + ctx_len
        start_page = (jnp.int32(0) if ctx_len is None
                      else ctx_len // page_size)
        x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
        x = x.astype(L.dt(cfg))
        x = shd(x, "batch", "seq", "act_embed")

        def write_pages(leaf, kv):
            # kv: [1, kvH, S, hd] tail K or V -> page-aligned scatter;
            # static page count, traced page ids (trash absorbs writes
            # past the allocation when the tail bucket overshoots)
            for j in range(n_tail_pages):
                piece = kv[:, :, j * page_size:(j + 1) * page_size, :]
                pid = table[start_page + j]
                leaf = jax.lax.dynamic_update_slice(
                    leaf, piece.astype(leaf.dtype), (pid, 0, 0, 0))
            return leaf

        def period_prefill(period_params, period_pool, x):
            new_pool = []
            for p_idx, pat in enumerate(cfg.block_pattern):
                bp = period_params[p_idx]
                cdt = L.dt(cfg)
                h = self._norm(bp["norm1"], x)
                q, k, v = L._qkv(bp["attn"], cfg, h, positions)
                pl = period_pool[p_idx]
                k_p = write_pages(pl["k"], k)
                v_p = write_pages(pl["v"], v)
                if ctx_len is None:
                    out = L._attend_causal(q, k, v, cfg, None,
                                           q_chunk=self.q_chunk)
                else:
                    # gather the full table (cached prefix + the tail
                    # pages just written); mask mirrors _attend_causal:
                    # row i sees absolute positions <= ctx_len + i, the
                    # rest contribute exact zeros
                    kvh, hd = k_p.shape[1], k_p.shape[3]
                    kg = jnp.swapaxes(k_p[table], 0, 1)
                    kg = kg.reshape(1, kvh, P * page_size, hd)
                    vg = jnp.swapaxes(v_p[table], 0, 1)
                    vg = vg.reshape(1, kvh, P * page_size, hd)
                    scores = L._grouped_scores(q, kg, cfg)
                    t_idx = jnp.arange(P * page_size)[None, None, :]
                    mask = t_idx <= positions[0][:, None]
                    scores = jnp.where(mask[None, None], scores, L.NEG_INF)
                    probs = jax.nn.softmax(scores, axis=-1).astype(vg.dtype)
                    out = jnp.einsum("bkgst,bkth->bkgsh", probs, vg)
                    out = out.reshape(1, cfg.num_heads, S, cfg.head_dim)
                h = L.attention_out(bp["attn"], cfg, h, out)
                x = x + h
                h = self._norm(bp["norm2"], x)
                if pat[1] == "moe":
                    h, _ = MOE.moe_fwd(bp["moe"], cfg, h)
                else:
                    h = L.mlp_fwd(bp["mlp"], cfg, h)
                x = x + h
                new_pool.append({"k": k_p, "v": v_p})
            return x, tuple(new_pool)

        if self.scan_layers:
            def body(x, xs):
                period_params, period_pool = xs
                x, ncs = period_prefill(period_params, period_pool, x)
                return x, ncs
            x, new_pool = jax.lax.scan(body, x, (params["layers"], pool))
        else:
            outs = []
            for i in range(cfg.num_periods):
                pp = jax.tree.map(lambda a: a[i], params["layers"])
                pc = jax.tree.map(lambda a: a[i], pool)
                x, ncs = period_prefill(pp, pc, x)
                outs.append(ncs)
            new_pool = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)

        x = self._norm(params["final_norm"], x)
        if last_pos is None:
            x_last = x[:, -1, :]
        else:
            x_last = jnp.take_along_axis(
                x, last_pos[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        w_out = (params["embed"]["tokens"].T if cfg.tie_embeddings
                 else params["lm_head"]["w"])
        logits = jnp.einsum("bd,dv->bv", x_last, w_out.astype(L.dt(cfg)))
        return logits.astype(jnp.float32), new_pool


@functools.lru_cache(maxsize=64)
def build_model(cfg: ModelConfig, scan_layers: bool = True,
                remat: bool = True, window: Optional[int] = None) -> Model:
    return Model(cfg, scan_layers=scan_layers, remat=remat, window=window)
