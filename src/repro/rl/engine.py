"""Continuous-batching inference engine (the paper's command-driven event
loop, Fig. 8): a slot-based engine over ``Model.decode_step`` that polls
ADD/ABORT commands between engine steps, so adding or aborting a trajectory
never stalls ongoing generation. This is the JAX stand-in for vLLM/SGLang in
the data plane, and the unit LLMProxy dispatches to.

Also implements the weight-sync hooks of the §6.2 protocol: ``suspend`` /
``resume`` / ``update_params`` (with KV-cache recomputation for in-flight
trajectories, step (5) of the protocol).

Prefill/decode disaggregation (§6.3, live counterpart of the simulator's
``pd_disagg`` mode): an engine can be constructed with
``role="prefill"`` — it runs the compute-bound prefill, samples the first
token, then packages the slot's KV cache as a :class:`KVHandoff` and emits
it through ``on_handoff`` instead of decoding — or ``role="decode"``,
which accepts handoffs via :meth:`inject` and runs the bandwidth-bound
decode loop. ``LLMProxy(pd_disagg=True)`` routes between the two roles.

The decode hot path is device-resident (§5.2/§6.3 make decode the
bandwidth-bound phase worth optimizing): each engine step runs
``steps_per_dispatch`` decode steps in ONE jit dispatch
(``Model.decode_block``, a ``lax.scan`` with on-device stop/length
masking and sampling inside the body), the KV-cache argument of every
compiled entry point is donated so XLA updates it in place instead of
copying ``[max_slots, max_len]`` worth of cache per step, and admission
prefill pads prompts to power-of-two buckets while writing the slot's
cache row directly (O(log max_len) compiled prefill shapes, no transient
batch-1 cache). Commands still drain between macro-steps, so ADD/ABORT
latency is bounded by one macro-step (K decode tokens per slot).

TP engine groups: constructed with a ``mesh`` (a per-engine (1, n)
("data", "model") group mesh), the engine executes SHARDED over its
device group — params and KV cache are placed with per-leaf
NamedShardings, every jit dispatch runs inside an ``axis_rules`` context
so the model's ``shd`` annotations become GSPMD constraints, KV-slot
handoffs gather to host numpy (portable across unequal group sizes),
and sharded weight sync assembles per-shard chunks straight into each
device's shard (:meth:`update_from_chunks` — no full per-engine copy).
Donation rules are UNCHANGED: the sharded cache is still donated
per-jit, params are never donated (mesh engines own a private placed
copy, but the host pytree stays shared with trainer/store/siblings).

Locking (machine-checked by ``python -m repro.analysis``; see the
``# guarded by:`` / ``# requires:`` annotations):

- ``_lock`` guards the command queue and result map (``_commands``,
  ``_results``): the cheap, contended producer/consumer state.
- ``_step_lock`` guards the slot/cache/param state and the stat counters:
  the expensive, step-granular state.
- **Canonical order: ``_step_lock`` -> ``_lock``** — the step path holds
  ``_step_lock`` and briefly takes ``_lock`` to drain commands or post
  results (``crash`` nests them the same way). Nothing may take
  ``_step_lock`` while holding ``_lock``.
- Cross-class: the proxy calls ``inject``/``add_request`` (which take
  only ``_lock``) while holding its own routing lock, and the engine
  calls ``on_finish``/``on_handoff`` hooks (which take the proxy's lock)
  while holding ``_step_lock``. That is only deadlock-free because no
  engine path takes ``_step_lock`` under the proxy's lock — which is why
  ``num_active``/``inflight_decode_tokens`` (read by the proxy under its
  lock) are deliberately lock-free racy reads, not ``_step_lock``
  acquisitions. Use :meth:`stats` for a consistent counter snapshot.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import threading
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import (SERVE_RULES, axis_rules,
                                        param_sharding, validate_group)
from repro.models.model import Model
from repro.obs import spans
from repro.rl.paged_kv import PagedKVAllocator, PrefixCache
from repro.rl.sampling import sample_mixed


@dataclasses.dataclass
class GenRequest:
    request_id: str
    prompt: List[int]
    max_new_tokens: int = 64
    temperature: float = 1.0
    top_k: Optional[int] = None
    stop_tokens: Sequence[int] = ()
    tag: str = "default"          # task-domain tag (hardware affinity, R1)


@dataclasses.dataclass
class GenResult:
    request_id: str
    tokens: List[int]             # newly generated tokens
    logprobs: List[float]
    finish_reason: str            # "stop" | "length" | "aborted"
    weight_version: int = 0
    prefill_tokens: int = 0
    decode_tokens: int = 0


@dataclasses.dataclass
class KVHandoff:
    """A prefilled trajectory in flight between a prefill-role and a
    decode-role engine: the request, the token/logprob state after the
    first sampled token, and the slot's cache pytree (batch axis == 1,
    extracted with ``Model.extract_cache_slot`` and gathered to HOST
    numpy arrays). The host gather is what makes the handoff portable
    across engines with *different* TP group sizes — injection re-shards
    the slot under the target engine's own mesh. Both engines must share
    the same model and ``max_len`` for the cache shapes to line up."""
    request: GenRequest
    tokens: List[int]             # prompt + first sampled token
    new_tokens: List[int]
    logprobs: List[float]
    pos: int
    start_version: int
    cache: object
    weight_version: int = 0       # weights the cache was prefilled under
    source: str = ""              # originating pool/engine (stats only)


@dataclasses.dataclass
class _Slot:
    active: bool = False
    request: Optional[GenRequest] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    new_tokens: List[int] = dataclasses.field(default_factory=list)
    logprobs: List[float] = dataclasses.field(default_factory=list)
    pos: int = 0                  # absolute position of next token slot
    start_version: int = 0        # weight version at trajectory start


ROLES = ("colocated", "prefill", "decode")


class EngineKilledError(RuntimeError):
    """Raised inside a step when :meth:`InferenceEngine.hard_kill` fired:
    the step unwinds (releasing ``_step_lock``) and ``step()`` converts
    the kill into a :meth:`crash` — the SIGKILL-plus-replacement-process
    model the watchdog uses to un-wedge a silently hung engine."""


def _slice_chunks(parts, dim: int, idx, shape) -> np.ndarray:
    """Assemble ``full[idx]`` from equal-size chunks of ``full`` along
    ``dim`` WITHOUT concatenating the full array: only the chunks
    overlapping the requested slice are touched. ``idx`` is the per-dim
    slice tuple a ``make_array_from_callback`` device callback receives;
    contiguous (step-1) slices only, which is all NamedSharding asks."""
    norm = [slice(*sl.indices(n)) for sl, n in zip(idx, shape)]
    if len(parts) == 1:
        return np.ascontiguousarray(np.asarray(parts[0])[tuple(norm)])
    csize = int(np.shape(parts[0])[dim])
    start, stop = norm[dim].start, norm[dim].stop
    pieces = []
    for c in range(start // csize, (stop - 1) // csize + 1):
        lo = max(start - c * csize, 0)
        hi = min(stop - c * csize, csize)
        sub = list(norm)
        sub[dim] = slice(lo, hi)
        pieces.append(np.asarray(parts[c])[tuple(sub)])
    out = (pieces[0] if len(pieces) == 1
           else np.concatenate(pieces, axis=dim))
    return np.ascontiguousarray(out)


def _one_device(params):
    """The one device every leaf of ``params`` lives on; None for host
    arrays or leaves spread over several devices. Reads each leaf's
    sharding, which a deleted array still has."""
    devices = set()
    for leaf in jax.tree.leaves(params):
        if not isinstance(leaf, jax.Array):
            return None
        devices |= leaf.sharding.device_set
    return next(iter(devices)) if len(devices) == 1 else None


class InferenceEngine:
    """Slot-based continuous batching engine.

    ``role`` selects the engine's place in the data plane: ``"colocated"``
    (default) serves prefill and decode monolithically; ``"prefill"`` only
    prefills and emits a ``KVHandoff`` per admitted request through
    ``on_handoff``; ``"decode"`` continues handed-off trajectories injected
    via :meth:`inject` (it can also serve raw ADDs as a fallback, but the
    proxy never routes them here in disaggregated mode).
    """

    def __init__(self, model: Model, params, *, max_slots: int = 8,
                 max_len: int = 512, seed: int = 0,
                 on_finish: Optional[Callable[[GenResult], None]] = None,
                 role: str = "colocated",
                 on_handoff: Optional[Callable[[KVHandoff], None]] = None,
                 steps_per_dispatch: int = 8, donate: bool = True,
                 bucketed_prefill: Optional[bool] = None,
                 mesh=None, shard_rules: Optional[Dict] = None,
                 paged: bool = False, page_size: int = 16,
                 num_pages: Optional[int] = None):
        """``steps_per_dispatch`` (K) is the decode macro-step size: K
        decode steps run per jit dispatch via ``Model.decode_block``.
        Larger K amortizes dispatch + host round-trip overhead but bounds
        command latency — an ABORT queued mid-macro-step takes effect up
        to K tokens later — so latency-sensitive serving should lower it
        (K=1 selects the legacy single-step dispatch). ``donate=False``
        disables KV-cache buffer donation (the un-donated copy-per-step
        baseline, kept for benchmarks/decode_hotpath.py).
        ``bucketed_prefill`` force-disables (False) the power-of-two
        prompt bucketing on stacks that support it — the
        one-compile-per-prompt-length seed behavior, kept for the same
        benchmark; None (default) enables it wherever valid.

        ``mesh`` (optional) is the engine's TP device group — a
        ``launch.mesh.make_group_mesh`` (1, n) ("data", "model") mesh.
        With a mesh the engine executes SHARDED over the group: params
        and the KV cache are placed with per-leaf NamedShardings under
        ``shard_rules`` (default SERVE_RULES), every jit traces inside an
        ``axis_rules`` context so the model's ``shd`` annotations become
        sharding constraints, and the engine owns a PRIVATE placed param
        copy (single-device engines keep sharing the caller's pytree).
        An n that shards no parameter dim raises (``validate_group``)
        instead of silently replicating the model n-fold."""
        if role not in ROLES:
            raise ValueError(f"role must be one of {ROLES}, got {role!r}")
        if steps_per_dispatch < 1:
            raise ValueError("steps_per_dispatch must be >= 1, got "
                             f"{steps_per_dispatch}")
        self.model = model
        self.params = params                       # guarded by: _step_lock
        self.mesh = mesh
        # the one device a mesh-less engine computes on: where the arrays
        # it was built over live. None for a mesh engine (it places a
        # sharded copy of its own) and for host arrays.
        self.device = None if mesh is not None else _one_device(params)
        self.tp_group = (int(np.prod(mesh.devices.shape))
                         if mesh is not None else 1)
        self._shard_rules = (dict(shard_rules) if shard_rules is not None
                             else dict(SERVE_RULES))
        if self.tp_group > 1:
            validate_group(params, self.tp_group, self._shard_rules,
                           model.cfg.name)
        self.max_slots = max_slots
        self.max_len = max_len
        self.on_finish = on_finish
        self.role = role                           # guarded by: _step_lock
        self.on_handoff = on_handoff
        # streaming hook (Rollout-as-a-Service tier): called under
        # _step_lock with (request_id, CUMULATIVE new_tokens, CUMULATIVE
        # logprobs) whenever a slot makes progress — on admission (first
        # sampled token), after each decode macro-step, and at finish.
        # Cumulative delivery makes the hook idempotent downstream
        # (TokenStream keeps per-request offsets), so replays across PD
        # handoffs / KV recomputes / FT re-injection are no-ops. The hook
        # must only take leaf locks (see TokenStream) — never _step_lock,
        # the proxy lock, or the service lock.
        self.on_progress: Optional[
            Callable[[str, List[int], List[float]], None]] = None
        self.steps_per_dispatch = steps_per_dispatch
        self.donate = donate
        # Admission prefill and KV recompute (protocol step (5)) pad
        # prompts to power-of-two buckets so XLA compiles O(log max_len)
        # shapes instead of one per distinct prompt length. Valid for
        # stacks of full attention and Gated DeltaNet: attention masks the
        # padded positions past last_pos causally (decode overwrites
        # them), and the DeltaNet prefill masks them out of its conv state
        # and recurrence (beta = g = 0 leaves the state unchanged). A
        # mamba/rwkv scan would take pad tokens into its state, and a
        # ring-buffered sliding window could wrap them over live entries.
        supported = model.window is None and all(
            mixer in ("attn", "gdn")
            for mixer, _ in model.cfg.block_pattern)
        self._bucketed_prefill = (supported if bucketed_prefill is None
                                  else bool(bucketed_prefill) and supported)
        # Paged KV (opt-in): per-slot dense cache rows are replaced by a
        # shared page pool + per-slot page tables, with a radix-style
        # prefix cache so redundant rollouts / multi-turn continuations
        # prefill once and FORK (rl/paged_kv.py). Greedy decode is
        # byte-identical to paged=False (see attention_decode_paged);
        # paged stays opt-in because SAMPLED (temp>0) streams are not:
        # decode dispatch compacts to the pow2-bucketed ACTIVE batch and
        # jax.random.categorical draws depend on the batch shape.
        self.paged = bool(paged)
        self.page_size = page_size
        if self.paged:
            if not model.supports_paged():
                raise ValueError(
                    f"{model.cfg.name}: paged KV requires an attention-"
                    "only stack with no sliding window")
            if page_size < 1 or page_size & (page_size - 1):
                raise ValueError(f"page_size must be a power of two, "
                                 f"got {page_size}")
            if max_len % page_size:
                raise ValueError(f"max_len={max_len} not divisible by "
                                 f"page_size={page_size}")
            self.num_pages = (num_pages if num_pages is not None
                              else (max_slots * max_len) // page_size)
            self._pages_per_slot = max_len // page_size
            self._trash_pid = self.num_pages       # extra pool row
            # page bookkeeping: all mutated under _step_lock (the
            # allocator's own lock is a leaf below _lock; PrefixCache is
            # lock-free and relies on _step_lock serialization)
            self._alloc = PagedKVAllocator(self.num_pages, page_size)  # guarded by: _step_lock
            self._prefix = PrefixCache(self._alloc, page_size)  # guarded by: _step_lock
            self._tables: List[List[int]] = [[] for _ in range(max_slots)]  # guarded by: _step_lock
            # page ids written on device since the last incremental
            # snapshot capture (FT dirty tracking)
            self._dirty = set()                    # guarded by: _step_lock
            self.shared_prefix_tokens = 0          # guarded by: _step_lock
        # width of the padded per-slot stop-token matrix fed to
        # decode_block; grows (power of two -> bounded recompiles) if a
        # request carries more stop tokens
        self._stop_width = 4                       # guarded by: _step_lock
        self.weight_version = 0                    # guarded by: _step_lock
        # bare flag, atomic under the GIL — see suspend() for the contract
        self.suspended = False
        self._key = jax.random.PRNGKey(seed)       # guarded by: _step_lock
        self._slots = [_Slot() for _ in range(max_slots)]  # guarded by: _step_lock
        # ("add", req) | ("abort", id) | ("inject", KVHandoff)
        self._commands = collections.deque()       # guarded by: _lock
        self._lock = threading.Lock()
        # serializes the mutators of _slots/_cache/params: step() (the pump
        # thread) vs update_params() (the control thread's weight sync).
        # The command queue has its own lock so add/abort/inject never
        # block on an in-flight decode step.
        self._step_lock = threading.Lock()
        self._results: Dict[str, GenResult] = {}   # guarded by: _lock
        # fit_spec drop events observed by THIS engine's traces/placements
        # (the module-wide one-shot ShardingDropWarning fires alongside);
        # bumped via the axis_rules on_drop hook, which only runs inside
        # _shard_ctx() — and every _shard_ctx() site holds _step_lock
        self.sharding_drops = 0                    # guarded by: _step_lock
        # host chunk bytes consumed by sharded weight syncs
        self.sync_bytes = 0                        # guarded by: _step_lock
        # weight versions taken as the trainer's own device arrays, and
        # versions placed from host arrays (pulled from the store)
        self.local_syncs = 0                       # guarded by: _step_lock
        self.store_syncs = 0                       # guarded by: _step_lock
        # param/cache placement: a mesh engine shards both over its group
        # (per-leaf NamedShardings; a sharded leaf never lands as a
        # whole-array copy on any one device). Done under _step_lock so
        # placement-time fit_spec drops funnel through _on_fit_drop with
        # the same lock trace-time drops hold.
        with self._step_lock:
            if self.mesh is not None:
                with self._shard_ctx():
                    self._param_shardings = param_sharding(
                        params, self.mesh, self._shard_rules)
                    self.params = jax.device_put(params,
                                                 self._param_shardings)
                    store = self._init_kv_store()
                    self._cache_shardings = model.cache_sharding(
                        store, self.mesh, self._shard_rules,
                        axes=(model.paged_cache_logical_axes()
                              if self.paged else None))
                    store = jax.device_put(store, self._cache_shardings)
            else:
                self._param_shardings = None
                self._cache_shardings = None
                store = self._init_kv_store()
            # the engine's KV store: dense per-slot cache (paged=False)
            # or the shared page pool (paged=True)
            if self.paged:
                self._pool = store                 # guarded by: _step_lock
                self._cache = None                 # guarded by: _step_lock
            else:
                self._cache = store                # guarded by: _step_lock
        # stats (steps/busy_steps count MACRO-steps, i.e. engine
        # iterations; decode_dispatches counts decode jit calls — with
        # K = steps_per_dispatch, dispatches/token converges to 1/K —
        # while prefill/decode token counters stay in TOKENS, which is
        # what proxy-level accounting and the rebalancer consume;
        # recomputes counts in-flight KV rebuilds (protocol (5)) and
        # crashes counts injected engine losses (repro.ft))
        self.steps = 0                             # guarded by: _step_lock
        self.busy_steps = 0                        # guarded by: _step_lock
        self.decode_dispatches = 0                 # guarded by: _step_lock
        self.prefill_tokens = 0                    # guarded by: _step_lock
        self.decode_tokens = 0                     # guarded by: _step_lock
        self.recomputes = 0                        # guarded by: _step_lock
        self.handoffs_out = 0                      # guarded by: _step_lock
        self.handoffs_in = 0                       # guarded by: _step_lock
        self.crashes = 0                           # guarded by: _step_lock
        # seconds from dispatch to the end of the readback of admission
        # prefills and decode macro-steps (the repro.engine.prefill /
        # .decode spans): the pumping thread waiting on the chip
        self.device_wait_s = 0.0                   # guarded by: _step_lock
        # MoE stacks: over decode blocks, the held experts some emitting
        # row routed to and the assignments that landed on held experts,
        # summed over scanned steps and layers
        self.moe_experts_touched = 0               # guarded by: _step_lock
        self.moe_tokens_here = 0                   # guarded by: _step_lock
        # liveness beat for the observability watchdog: bumped at the END
        # of every step() AFTER _step_lock is released — a bare lock-free
        # counter (atomic under the GIL) so the watchdog can read it
        # while a wedged step holds _step_lock forever. A beat that stops
        # advancing while has_pending is True is the hang signal.
        self.beats = 0
        # hard-kill latch + test-only wedge hook (see hard_kill). Both
        # bare: hard_kill must work from the watchdog thread while the
        # step path is hung inside _step_lock.
        self._kill_evt = threading.Event()
        self._prestep_hook: Optional[Callable[["InferenceEngine"],
                                              None]] = None
        # requests rejected at submit because prompt+budget can NEVER fit
        # max_len (bugfix: formerly conflated with "no free slot" and
        # queued forever). Guarded by _lock, not _step_lock: the
        # rejection runs synchronously on the submitter's thread, which
        # may hold the proxy's routing state and must not take
        # _step_lock (cross-class ordering, see module docstring).
        self.rejected_too_long = 0                 # guarded by: _lock
        self._build_jit()

    def _init_kv_store(self):   # requires: _step_lock
        """Fresh zeroed KV store (host layout): the dense per-slot cache,
        or the page pool plus one trash row absorbing padded-table
        writes/gathers."""
        if self.paged:
            return self.model.init_paged_pool(self.num_pages + 1,
                                              self.page_size)
        return self.model.init_cache(self.max_slots, self.max_len)

    # ------------------------------------------------------------------
    def _build_jit(self):
        model = self.model
        # Donate the cache argument (index 2 in every entry point): the
        # engine owns exactly one live cache reference (always rebound from
        # the jit result under _step_lock), so XLA may alias input to
        # output and update the [max_slots, max_len] cache in place
        # instead of copying it per call. Params are NOT donated: the same
        # param buffers are shared with the trainer, the weight store, and
        # sibling engines (build_pd_proxy passes one pytree to all of
        # them), so donating would invalidate them for everyone else. The
        # converse holds for the trainer: its step must never donate a
        # state whose params an engine holds, and a mesh-less engine on
        # the trainer's device holds the trainer's own arrays after every
        # barrier (update_params' device path, which refuses a deleted
        # leaf).
        donate = (2,) if self.donate else ()

        @functools.partial(jax.jit, donate_argnums=donate)
        def _decode(params, tokens, cache, positions, key, temperature):
            logits, cache = model.decode_step(params, tokens, cache,
                                              positions)
            toks, lps = sample_mixed(key, logits, temperature)
            return toks, lps, cache

        K = self.steps_per_dispatch

        @functools.partial(jax.jit, donate_argnums=donate)
        def _decode_block(params, tokens, cache, positions, key,
                          temperatures, stop_ids, budgets):
            # derive the K per-step keys ON DEVICE with the same
            # sequential split chain _next_key walks host-side, so (a)
            # sampled streams stay byte-identical across
            # steps_per_dispatch settings and (b) the macro-step costs
            # one dispatch total instead of K host-side splits plus one
            def split_body(c, _):
                c, sub = jax.random.split(c)
                return c, sub
            new_key, keys = jax.lax.scan(split_body, key, None, length=K)
            toks, lps, emitted, cache, stats = model.decode_block(
                params, tokens, cache, positions, keys, temperatures,
                stop_ids, budgets, sample_fn=sample_mixed)
            return toks, lps, emitted, cache, new_key, stats

        @functools.partial(jax.jit, donate_argnums=donate)
        def _prefill_into_slot(params, tokens, cache, slot, last_pos, key,
                               temperature):
            """tokens: [1, S]; writes the slot's cache row IN PLACE
            (Model.prefill slot mode — no transient batch-1 cache) and
            samples the first generated token from the last prompt
            position."""
            logits, cache = model.prefill(params, tokens, cache,
                                          last_pos=last_pos, slot=slot)
            toks, lps = sample_mixed(key, logits, temperature)
            return toks, lps, cache

        self._decode_jit = _decode
        self._decode_block_jit = _decode_block
        self._prefill_jit = _prefill_into_slot
        self._sample = sample_mixed
        if not self.paged:
            return
        page = self.page_size

        @functools.partial(jax.jit, donate_argnums=donate)
        def _decode_block_paged(params, tokens, pool, tables, positions,
                                key, temperatures, stop_ids, budgets):
            def split_body(c, _):
                c, sub = jax.random.split(c)
                return c, sub
            new_key, keys = jax.lax.scan(split_body, key, None, length=K)
            toks, lps, emitted, pool, stats = model.decode_block_paged(
                params, tokens, pool, tables, positions, keys,
                temperatures, stop_ids, budgets, sample_fn=sample_mixed,
                page_size=page)
            return toks, lps, emitted, pool, new_key, stats

        @functools.partial(jax.jit, donate_argnums=donate)
        def _prefill_paged(params, tokens, pool, table, last_rel, key,
                           temperature):
            logits, pool = model.prefill_paged(
                params, tokens, pool, table, page, last_pos=last_rel)
            toks, lps = sample_mixed(key, logits, temperature)
            return toks, lps, pool

        @functools.partial(jax.jit, donate_argnums=donate)
        def _prefill_paged_fork(params, tokens, pool, table, ctx_len,
                                last_rel, key, temperature):
            logits, pool = model.prefill_paged(
                params, tokens, pool, table, page, last_pos=last_rel,
                ctx_len=ctx_len)
            toks, lps = sample_mixed(key, logits, temperature)
            return toks, lps, pool

        self._decode_block_paged_jit = _decode_block_paged
        self._prefill_paged_jit = _prefill_paged
        self._prefill_paged_fork_jit = _prefill_paged_fork

    def _shard_ctx(self):
        """axis_rules context for tracing and placement: activates the
        group mesh + logical rules (so ``Model``'s ``shd`` annotations
        become NamedSharding constraints) plus the per-engine drop
        counter. A no-op nullcontext for single-device engines. Only
        entered with ``_step_lock`` held — the on_drop hook relies on
        it."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return axis_rules(self.mesh, self._shard_rules,
                          on_drop=self._on_fit_drop)

    def _on_fit_drop(self):   # requires: _step_lock
        self.sharding_drops += 1

    def _next_key(self):   # requires: _step_lock
        self._key, k = jax.random.split(self._key)
        return k

    # ------------------------------------------------------------------
    # command interface (thread-safe)
    # ------------------------------------------------------------------
    def add_request(self, req: GenRequest):
        if len(req.prompt) + req.max_new_tokens > self.max_len:
            # unservable at ANY occupancy: queueing it would either wedge
            # admission forever (the old conflated `_admit` check) or
            # waste a round trip to the drain-time backstop — reject NOW,
            # on the submitter's thread, with a counted aborted result
            self._reject_too_long(req)
            return
        with self._lock:
            self._commands.append(("add", req))

    def _reject_too_long(self, req: GenRequest):
        """Emit the aborted result for a request whose prompt+budget can
        never fit ``max_len``. Takes only ``_lock`` — callable from
        ``add_request`` on a submitter thread that may sit under proxy
        routing state (never ``_step_lock``; see cross-class ordering)."""
        # advisory racy read for result metadata: exact versioning is
        # meaningless for a request that never touched the slots
        res = GenResult(request_id=req.request_id, tokens=[], logprobs=[],
                        finish_reason="aborted",
                        # analysis: ignore[guarded-attr] advisory read
                        weight_version=self.weight_version,
                        prefill_tokens=0, decode_tokens=0)
        with self._lock:
            self.rejected_too_long += 1
            self._results[res.request_id] = res
        if self.on_finish:
            self.on_finish(res)

    def inject(self, handoff: KVHandoff):
        """Queue a prefilled trajectory for decode (PD disaggregation)."""
        with self._lock:
            self._commands.append(("inject", handoff))

    def abort(self, request_id: str):
        with self._lock:
            self._commands.append(("abort", request_id))

    def set_role(self, role: str):
        """Switch the engine's data-plane role (dynamic rebalancing). The
        caller (LLMProxy) is responsible for draining queued commands and
        in-flight slots first — see ``extract_pending`` and
        ``drain_active_handoffs`` — and for installing ``on_handoff`` when
        the new role is ``"prefill"``."""
        if role not in ROLES:
            raise ValueError(f"role must be one of {ROLES}, got {role!r}")
        with self._step_lock:
            self.role = role

    def extract_pending(self) -> List:
        """Atomically remove and return all queued commands (role switch:
        the proxy re-dispatches them through its routing tables)."""
        with self._lock:
            cmds = list(self._commands)
            self._commands.clear()
        return cmds

    def drain_active_handoffs(self) -> List[KVHandoff]:
        """Package every in-flight slot as a KVHandoff and free it — the
        migration half of a decode->prefill role switch. Serialized against
        ``step``/``update_params`` so no slot is mid-decode while its cache
        is extracted."""
        with self._step_lock:
            return [self._package_handoff(i)
                    for i, s in enumerate(self._slots) if s.active]

    # ------------------------------------------------------------------
    # fault tolerance (repro.ft): snapshot + failure injection
    # ------------------------------------------------------------------
    def snapshot_slots(self) -> List[KVHandoff]:
        """Non-destructive copy of every in-flight slot as a KVHandoff —
        the engine half of a rollout snapshot. Unlike
        ``drain_active_handoffs`` the slots stay live; the engine keeps
        decoding after the snapshot returns."""
        with self._step_lock:
            return [self._peek_handoff(i)
                    for i, s in enumerate(self._slots) if s.active]

    def snapshot_commands(self) -> List:
        """Copy of the queued-but-unprocessed commands (ADD / INJECT /
        ABORT), for snapshotting requests that were dispatched but never
        admitted."""
        with self._lock:
            return list(self._commands)

    def snapshot_rng(self):
        """The engine's PRNG chain head as a host array (snapshot).
        Serialized against the step loop: half-advanced key reads would
        make a restored snapshot replay a different sample stream."""
        with self._step_lock:
            return np.asarray(self._key)

    def restore_rng(self, key):
        with self._step_lock:
            self._key = jnp.asarray(key)

    def crash(self):
        """Simulate losing this engine's process: every in-flight slot,
        queued command, undelivered result, and the whole KV cache are
        gone (the engine object itself survives, standing in for a
        restarted replacement bound to the same devices). The proxy route
        table still points dangling requests here — recovery re-injects
        them from the latest snapshot (see ``repro.ft.supervisor``)."""
        with self._step_lock:
            with self._lock:
                self._commands.clear()
                self._results.clear()
            self._slots = [_Slot() for _ in range(self.max_slots)]
            store = self._init_kv_store()
            if self.mesh is not None:
                # the reborn replacement binds the same device group, so
                # its fresh cache takes the same shardings
                store = jax.device_put(store, self._cache_shardings)
            if self.paged:
                self._pool = store
                # the pool metadata dies with the process: fresh
                # allocator / prefix cache / tables, no dirty pages
                self._alloc = PagedKVAllocator(self.num_pages,
                                               self.page_size)
                self._prefix = PrefixCache(self._alloc, self.page_size)
                self._tables = [[] for _ in range(self.max_slots)]
                self._dirty = set()
            else:
                self._cache = store
            self.crashes += 1

    def hard_kill(self):
        """Kill switch for a silently hung engine (watchdog recovery
        path). Sets a bare latch WITHOUT taking any lock — a wedged
        ``step()`` holds ``_step_lock`` forever, so a lock-taking kill
        would hang the killer too. The step path checks the latch at its
        pre-step boundary and raises :class:`EngineKilledError`; if the
        step is blocked inside a (test-hook) wedge, setting the event
        also unblocks hooks that wait on it. ``step()`` converts the
        unwind into :meth:`crash` — the same lost-process state the FT
        plane already knows how to recover."""
        self._kill_evt.set()

    def suspend(self):
        """Stop admitting new requests; in-flight slots are preserved.
        A bare flag write (atomic under the GIL): the pump thread reads it
        inside ``step``; callers needing a hard barrier (nothing decoding
        while weights swap) hold the runner-level pump lock across
        suspend → update → resume."""
        self.suspended = True

    def resume(self):
        self.suspended = False

    def shares_device(self, params) -> bool:
        """Whether this engine can take ``params`` as they are: it has no
        mesh, and every leaf of ``params`` is a device array on the
        engine's one device (the trainer's own arrays, on its chip)."""
        return self.device is not None and _one_device(params) == self.device

    def update_params(self, params, version: int,
                      recompute_caches: bool = True):
        """Weight sync (protocol steps (3)+(5)): swap weights and rebuild
        each in-flight trajectory's cache under the new weights.

        Device path: where :meth:`shares_device` holds, the engine takes
        ``params`` as they are, with no copy (span
        ``repro.weights.local``, counter ``local_syncs``); a deleted
        (donated) leaf raises ``RuntimeError`` here rather than inside a
        later dispatch. Otherwise ``params`` (host arrays pulled from the
        store) are placed on the engine's devices (span
        ``repro.weights.pull``, counter ``store_syncs``).

        No-op when ``version`` equals the engine's current weight version
        (e.g. iteration 0, where the store still holds the weights the
        engine was built with): re-prefilling every in-flight cache under
        identical weights would burn a full prefill per slot for nothing.
        The version check happens under ``_step_lock``: checked outside,
        two concurrent syncs could interleave check-then-swap and leave
        params and weight_version from different versions.
        """
        with self._step_lock:
            if version == self.weight_version:
                return
            if self.shares_device(params):
                with spans.span("repro.weights.local"):
                    if any(leaf.is_deleted()
                           for leaf in jax.tree.leaves(params)):
                        raise RuntimeError(
                            f"weight version {version} has a deleted "
                            "leaf: the trainer's step donated params an "
                            "engine on its device serves")
                self.local_syncs += 1
            else:
                with spans.span("repro.weights.pull"):
                    if self.mesh is not None:
                        # per-leaf sharded placement: each leaf lands
                        # under its NamedSharding (device_put splits host
                        # leaves into shards), never as a whole-array
                        # copy on one device of the group
                        with self._shard_ctx():
                            params = jax.device_put(params,
                                                    self._param_shardings)
                    else:
                        # the store hands out host arrays: place them
                        # once here, on the engine's device, not on every
                        # dispatch that reads them
                        params = jax.device_put(params, self.device)
                self.store_syncs += 1
            self.params = params
            self.weight_version = version
            if recompute_caches:
                if self.paged:
                    # cached prefix KV was computed under the OLD
                    # weights: a post-sync fork of it would silently mix
                    # versions in one trajectory
                    self._prefix.clear()
                self._recompute_active()

    def update_from_chunks(self, chunks, version: int,
                           recompute_caches: bool = True):
        """Sharded weight sync: swap in a new version delivered as
        PER-SHARD host chunks (``weightstore.pull_param_chunks`` format —
        one ``(dim, parts)`` entry per param leaf, ``dim=None`` for
        unchunked leaves). A mesh engine assembles each leaf directly
        into its NamedSharding via ``jax.make_array_from_callback``:
        every device's callback slices just ITS shard out of the chunk
        list, so a sharded leaf is never materialized whole — on host or
        on any single device — even when the store's chunk count differs
        from this engine's TP degree (unequal PD group sizes). A
        single-device engine concatenates chunks. Same same-version no-op
        and in-flight KV recompute semantics as :meth:`update_params`."""
        with self._step_lock:
            if version == self.weight_version:
                return
            treedef = jax.tree.structure(self.params)
            shardings = (jax.tree.leaves(self._param_shardings)
                         if self.mesh is not None
                         else [None] * len(chunks))
            with spans.span("repro.weights.pull"):
                leaves = [self._assemble_leaf(dim, parts, shd)
                          for (dim, parts), shd in zip(chunks, shardings)]
            self.store_syncs += 1
            self.params = jax.tree.unflatten(treedef, leaves)
            self.weight_version = version
            if recompute_caches:
                if self.paged:
                    # stale-version prefix KV, same as update_params
                    self._prefix.clear()
                self._recompute_active()

    def _recompute_active(self):   # requires: _step_lock
        """Protocol step (5): rebuild every in-flight slot's cache under
        the weights just installed."""
        with spans.span("repro.engine.recompute"):
            for i, s in enumerate(self._slots):
                if s.active and s.pos > 1:
                    self._reprefill_slot(i)

    def _assemble_leaf(self, dim, parts, sharding):   # requires: _step_lock
        """One param leaf from its host chunks. ``sync_bytes`` counts the
        host bytes actually consumed: for a sharded leaf the device
        callbacks sum to ~1x the leaf (split across the group), for a
        replicated leaf on a group they sum to group-x — which is the
        honest cost of replication the benchmark reports."""
        if dim is not None and len(parts) > 1:
            shape = list(np.shape(parts[0]))
            shape[dim] *= len(parts)
            shape = tuple(shape)
        else:
            shape = tuple(np.shape(parts[0]))
        if sharding is None:
            arr = (np.concatenate([np.asarray(p) for p in parts], axis=dim)
                   if len(parts) > 1 else np.asarray(parts[0]))
            self.sync_bytes += int(arr.nbytes)
            return jnp.asarray(arr)

        def cb(idx):
            piece = _slice_chunks(parts, dim, idx, shape)
            self.sync_bytes += int(piece.nbytes)
            return piece

        return jax.make_array_from_callback(shape, sharding, cb)

    def param_device_bytes(self) -> Dict[str, int]:
        """Parameter bytes resident per device (addressable shards) — the
        no-full-copy accounting: at TP degree g, sharded leaves
        contribute 1/g per device, so no device of a useful group holds
        the full parameter footprint."""
        with self._step_lock:
            out: Dict[str, int] = {}
            for leaf in jax.tree.leaves(self.params):
                if hasattr(leaf, "addressable_shards"):
                    for sh in leaf.addressable_shards:
                        d = str(sh.device)
                        out[d] = out.get(d, 0) + int(sh.data.nbytes)
                else:
                    out["host"] = (out.get("host", 0)
                                   + int(np.asarray(leaf).nbytes))
            return out

    def _bucket_len(self, n: int) -> int:
        b = 16
        while b < n:
            b <<= 1
        return min(b, self.max_len)

    def _prefill_slot(self, i: int, temperature: float,
                      ctx_tokens: int = 0,
                      upto: Optional[int] = None):   # requires: _step_lock
        """Fill slot ``i``'s cache row from its tokens[:upto] (default
        tokens[:pos]) — shared by first admission and the protocol-(5) KV
        recompute. Where ``_bucketed_prefill`` holds, the prompt is padded
        to a power-of-two bucket (the model masks positions past the last
        real one), so XLA compiles O(log max_len) prefill shapes instead
        of one per distinct prompt length. Returns the (token, logprob)
        sampled at the true last prompt position.

        Paged engines prefill only the TAIL past ``ctx_tokens`` cached
        prefix tokens (a page multiple, 0 = fresh prompt): the forked
        prefix pages already hold its KV. The tail is padded to a page-
        multiple bucket; overshoot past the slot's allocation writes to
        the trash row."""
        s = self._slots[i]
        upto = s.pos if upto is None else upto
        if not self.paged:
            toks = s.tokens[:upto]
            if self._bucketed_prefill:
                toks = toks + [0] * (self._bucket_len(len(toks)) - len(toks))
            tok_arr = jnp.asarray([toks], jnp.int32)
            last = jnp.asarray([upto - 1], jnp.int32)
            with self._shard_ctx():
                tok, lp, self._cache = self._prefill_jit(
                    self.params, tok_arr, self._cache, i, last,
                    self._next_key(), jnp.float32(temperature))
            return tok, lp
        page = self.page_size
        m = ctx_tokens
        tail = s.tokens[m:upto]
        n = len(tail)
        if self._bucketed_prefill:
            sb = max(self._bucket_len(n), page)
        else:
            sb = -(-n // page) * page
        # never index page-table slots past the table width: the real
        # tail region always fits ([m, pos) is within max_len), only the
        # bucket overshoot is trimmed
        sb = min(sb, self.max_len - m)
        tail = tail + [0] * (sb - n)
        tok_arr = jnp.asarray([tail], jnp.int32)
        tbl = jnp.asarray(self._full_table(i))
        last_rel = jnp.asarray([upto - 1 - m], jnp.int32)
        with self._shard_ctx():
            if m == 0:
                tok, lp, self._pool = self._prefill_paged_jit(
                    self.params, tok_arr, self._pool, tbl, last_rel,
                    self._next_key(), jnp.float32(temperature))
            else:
                tok, lp, self._pool = self._prefill_paged_fork_jit(
                    self.params, tok_arr, self._pool, tbl, jnp.int32(m),
                    last_rel, self._next_key(), jnp.float32(temperature))
        first = m // page
        self._dirty.update(self._tables[i][first: first + sb // page])
        return tok, lp

    def _reprefill_slot(self, i: int):   # requires: _step_lock
        if self.paged:
            # the recompute rewrites every page of the slot from position
            # 0 — give it exclusive pages first so the rewrite cannot
            # mutate pages shared with the prefix cache or other slots
            self._cow_slot_pages(i)
        # the cache holds tokens[:pos-1]: the last token is fed by the next
        # decode step, which writes its own entry (and advances a recurrent
        # state by it once). The sampled token is unused.
        self._prefill_slot(i, -1.0, upto=self._slots[i].pos - 1)
        self.recomputes += 1

    def _cow_slot_pages(self, i: int):   # requires: _step_lock
        """Copy-on-write every shared page of slot ``i``'s table. Pool
        pressure first evicts prefix-cache pages; if the pool is STILL
        exhausted the slot falls back to rewriting the shared page in
        place — safe for the weight-sync path because the prefix cache
        was cleared and every sharing slot is itself recomputed to
        byte-identical contents under the same new weights."""
        tbl = self._tables[i]
        for j, pid in enumerate(tbl):
            if self._alloc.refcount(pid) <= 1:
                continue
            while (self._alloc.free_pages == 0
                   and self._prefix.cached_pages > 0):
                self._prefix.evict(1)
            new = self._alloc.cow(pid)
            if new is not None and new != pid:
                tbl[j] = new
                self._dirty.add(new)

    def _grow_stop_width(self, stop_tokens: Sequence[int]):   # requires: _step_lock
        while len(stop_tokens) > self._stop_width:
            self._stop_width *= 2

    # ------------------------------------------------------------------
    def _admit(self, req: GenRequest) -> bool:   # requires: _step_lock
        # too-long requests never reach here: add_request rejects them at
        # submit and _drain_commands backstops queue-restored ones, so a
        # False return always means "retry later", never "can never fit"
        free = [i for i, s in enumerate(self._slots) if not s.active]
        if not free:
            return False
        i = free[0]
        shared = 0
        if self.paged:
            table, shared = self._alloc_slot_pages(req)
            if table is None:
                return False      # pool pressure: defer like no-free-slot
            self._tables[i] = table
        s = self._slots[i]
        s.active = True
        s.request = req
        s.tokens = list(req.prompt)
        s.new_tokens, s.logprobs = [], []
        s.pos = len(req.prompt)
        s.start_version = self.weight_version
        self._grow_stop_width(req.stop_tokens)
        with spans.phase("repro.engine.prefill") as ph:
            tok, lp = self._prefill_slot(i, req.temperature,
                                         ctx_tokens=shared)
            tok, lp = int(tok[0]), float(lp[0])
        self.device_wait_s += ph.seconds
        self.prefill_tokens += s.pos - shared   # real NEW tokens prefilled
        if self.paged:
            self.shared_prefix_tokens += shared
            # register the freshly-prefilled prompt pages so concurrent
            # admissions of shared-prompt requests fork them immediately
            self._prefix.insert(req.prompt, self._tables[i])
        self._append_token(i, tok, lp)
        # stream the first sampled token (idempotent if _append_token
        # already finished the request and _finish emitted it)
        self._emit_progress(req.request_id, s)
        if self.role == "prefill" and s.active:
            # still generating after the first token: migrate the slot's
            # cache to a decode-role engine instead of decoding here
            self._emit_handoff(i)
        return True

    def _alloc_slot_pages(self, req: GenRequest):   # requires: _step_lock
        """Reserve slot pages for ``req`` up-front: EVERY page the request
        can touch (prompt + full decode budget, capped at max_len) is
        allocated at admission, so a mid-flight decode step can never hit
        an out-of-pages failure. Shared-prefix pages come from the radix
        cache (incref'd, never written by this slot); the rest are fresh
        private pages. Returns ``(table, shared_tokens)`` or
        ``(None, 0)`` when the pool — even after evicting cached prefix
        pages — cannot cover the request (caller defers it)."""
        page = self.page_size
        total = min(len(req.prompt) + req.max_new_tokens, self.max_len)
        n_pages = -(-total // page)
        matched = self._prefix.match(req.prompt)
        # the tail (>= 1 prompt token: decode needs a real last position
        # to prefill logits from) always starts on a fresh private page
        matched = matched[: (len(req.prompt) - 1) // page]
        self._alloc.incref(matched)   # pin before eviction can run
        need = n_pages - len(matched)
        while (self._alloc.free_pages < need
               and self._prefix.cached_pages > 0):
            self._prefix.evict(1)
        priv = self._alloc.alloc(need)
        if priv is None:
            self._alloc.decref(matched)
            return None, 0
        self._dirty.update(priv)
        return matched + priv, len(matched) * page

    def _full_table(self, i: int) -> np.ndarray:   # requires: _step_lock
        """Slot ``i``'s page table padded to full width with the trash
        page id — the fixed-shape form every paged jit consumes (padded
        gathers read the trash row and are masked; padded writes land in
        the trash row)."""
        tbl = np.full((self._pages_per_slot,), self._trash_pid, np.int32)
        pids = self._tables[i][: self._pages_per_slot]
        tbl[: len(pids)] = pids
        return tbl

    def _release_slot_pages(self, i: int):   # requires: _step_lock
        """Return slot ``i``'s pages to the pool — but first hand the
        finished trajectory to the prefix cache so a multi-turn
        continuation (same conversation + new env tokens) forks it
        instead of re-prefilling. Only guaranteed-WRITTEN positions are
        cacheable: the device has KV for tokens[:pos-1] (the final
        sampled token was never fed), so the insert stops at the last
        full page below pos-1."""
        if not self.paged:
            return
        tbl = self._tables[i]
        if not tbl:
            return
        s = self._slots[i]
        done = max(s.pos - 1, 0)
        if done >= self.page_size:
            self._prefix.insert(s.tokens[:done], tbl)
        self._alloc.decref(tbl)
        self._tables[i] = []

    def _peek_handoff(self, i: int) -> KVHandoff:   # requires: _step_lock
        """Freeze slot ``i`` into a KVHandoff WITHOUT freeing the slot.
        ``extract_cache_slot`` produces fresh arrays (a dynamic slice), so
        the handoff stays valid even after later donated dispatches
        invalidate the engine's own cache buffer. The slot is gathered to
        HOST numpy (``jax.device_get`` all-gathers a sharded slot's
        shards): the host copy is the portable interchange format — it
        injects into any engine regardless of that engine's TP group
        size, and the FT snapshotter serializes it as-is. A paged engine
        gathers the slot's pages back into the SAME dense layout, so the
        handoff format — and everything downstream of it (unequal-TP
        re-shard, FT serialization, paged<->dense handoffs) — is
        unchanged."""
        s = self._slots[i]
        if self.paged:
            cache = jax.device_get(self.model.paged_to_dense_slot(
                self._pool, jnp.asarray(self._full_table(i))))
        else:
            cache = jax.device_get(self.model.extract_cache_slot(
                self._cache, i))
        return KVHandoff(
            request=s.request, tokens=list(s.tokens),
            new_tokens=list(s.new_tokens), logprobs=list(s.logprobs),
            pos=s.pos, start_version=s.start_version,
            cache=cache, weight_version=self.weight_version)

    def _package_handoff(self, i: int) -> KVHandoff:   # requires: _step_lock
        """Freeze slot ``i`` into a KVHandoff and free the slot."""
        s = self._slots[i]
        handoff = self._peek_handoff(i)
        self._release_slot_pages(i)
        s.active = False
        s.request = None
        return handoff

    def _emit_handoff(self, i: int):   # requires: _step_lock
        if self.on_handoff is None:
            raise RuntimeError(
                "prefill-role engine needs an on_handoff hook "
                "(set by LLMProxy(pd_disagg=True))")
        handoff = self._package_handoff(i)
        self.handoffs_out += 1
        self.on_handoff(handoff)

    def _admit_handoff(self, handoff: KVHandoff) -> bool:   # requires: _step_lock
        free = [i for i, s in enumerate(self._slots) if not s.active]
        if not free:
            return False
        i = free[0]
        if self.paged:
            # all-private pages: the handoff carries opaque dense KV, so
            # there is no token<->page correspondence to share from (the
            # finished slot will still be INSERTED for future forks)
            req = handoff.request
            total = min(len(req.prompt) + req.max_new_tokens, self.max_len)
            need = -(-total // self.page_size)
            while (self._alloc.free_pages < need
                   and self._prefix.cached_pages > 0):
                self._prefix.evict(1)
            pids = self._alloc.alloc(need)
            if pids is None:
                return False
            self._tables[i] = pids
            self._dirty.update(pids)
        s = self._slots[i]
        s.active = True
        s.request = handoff.request
        s.tokens = list(handoff.tokens)
        s.new_tokens = list(handoff.new_tokens)
        s.logprobs = list(handoff.logprobs)
        s.pos = handoff.pos
        s.start_version = handoff.start_version
        self._grow_stop_width(handoff.request.stop_tokens)
        if handoff.weight_version != self.weight_version:
            # the handoff sat in the command queue across a weight sync:
            # protocol step (5) only recomputes ACTIVE slots, so rebuild
            # this cache under the current weights instead of injecting
            # the stale one
            self._reprefill_slot(i)
        elif self.paged:
            # scatter the dense slot image into this slot's pages (one
            # eager page-granular scatter; GSPMD handles a sharded pool)
            self._pool = self.model.dense_slot_to_pages(
                self._pool,
                jax.tree.map(jnp.asarray, handoff.cache),
                jnp.asarray(self._full_table(i)))
        else:
            self._cache = self.model.inject_cache_slot(self._cache,
                                                       handoff.cache, i)
        self.handoffs_in += 1
        return True

    def _emit_progress(self, rid: str, s: _Slot):   # requires: _step_lock
        """Stream slot progress: the CUMULATIVE new-token list (survives
        ``_finish``, which clears only ``request``/``active``)."""
        if self.on_progress is not None and s.new_tokens:
            self.on_progress(rid, list(s.new_tokens), list(s.logprobs))

    def _emit_step_progress(self, active: List[int]):   # requires: _step_lock
        """Post-macro-step streaming for slots still generating (finished
        slots already emitted their final cumulative state in _finish)."""
        if self.on_progress is None:
            return
        for i in active:
            s = self._slots[i]
            if s.active:
                self._emit_progress(s.request.request_id, s)

    def _append_token(self, i: int, tok: int, lp: float):   # requires: _step_lock
        s = self._slots[i]
        s.tokens.append(tok)
        s.new_tokens.append(tok)
        s.logprobs.append(lp)
        s.pos += 1
        req = s.request
        if tok in req.stop_tokens:
            self._finish(i, "stop")
        elif len(s.new_tokens) >= req.max_new_tokens or s.pos >= self.max_len:
            self._finish(i, "length")

    def _finish(self, i: int, reason: str):   # requires: _step_lock
        s = self._slots[i]
        res = GenResult(
            request_id=s.request.request_id,
            tokens=list(s.new_tokens), logprobs=list(s.logprobs),
            finish_reason=reason, weight_version=self.weight_version,
            prefill_tokens=len(s.request.prompt),
            decode_tokens=len(s.new_tokens))
        with self._lock:
            self._results[res.request_id] = res
        self._release_slot_pages(i)
        s.active = False
        s.request = None
        # final cumulative stream push BEFORE on_finish: the proxy's
        # finish hook unregisters the request's stream, so this ordering
        # guarantees the stream saw every token by the time it closes
        self._emit_progress(res.request_id, s)
        if self.on_finish:
            self.on_finish(res)

    @staticmethod
    def _cmd_request_id(cmd) -> Optional[str]:
        kind, payload = cmd
        if kind == "add":
            return payload.request_id
        if kind == "inject":
            return payload.request.request_id
        return None

    def _emit_aborted_pending(self, cmd):   # requires: _step_lock
        """A never-admitted ADD/INJECT was aborted: still emit a result so
        the proxy/EnvManager callback chain observes the cancellation."""
        kind, payload = cmd
        if kind == "add":
            res = GenResult(request_id=payload.request_id, tokens=[],
                            logprobs=[], finish_reason="aborted",
                            weight_version=self.weight_version,
                            prefill_tokens=0, decode_tokens=0)
        else:
            # the handoff carries already-sampled tokens: report them as
            # decode_tokens so proxy/runner token accounting balances
            res = GenResult(request_id=payload.request.request_id,
                            tokens=list(payload.new_tokens),
                            logprobs=list(payload.logprobs),
                            finish_reason="aborted",
                            weight_version=self.weight_version,
                            prefill_tokens=len(payload.request.prompt),
                            decode_tokens=len(payload.new_tokens))
        with self._lock:
            self._results[res.request_id] = res
        if self.on_finish:
            self.on_finish(res)

    def _abort(self, request_id: str):   # requires: _step_lock
        for i, s in enumerate(self._slots):
            if s.active and s.request.request_id == request_id:
                self._finish(i, "aborted")
                return
        # not yet admitted: drop from pending adds/injects
        dropped = None
        with self._lock:
            kept = collections.deque()
            for c in self._commands:
                if dropped is None and self._cmd_request_id(c) == request_id:
                    dropped = c
                else:
                    kept.append(c)
            self._commands = kept
        if dropped is not None:
            self._emit_aborted_pending(dropped)

    def _drain_commands(self):   # requires: _step_lock
        """Process queued commands. ABORTs always drain — a blocked ADD or
        INJECT (no free slot / suspended) defers itself and every later
        admission (FIFO preserved) but must not head-of-line-block
        cancellations queued behind it."""
        # idle-pump fast path: reading the deque's emptiness is atomic
        # under the GIL, so an empty queue costs O(1) with no lock
        # acquisition or deque rebuild (the common case in every pump); a
        # command enqueued concurrently is seen by the next pump at worst
        # analysis: ignore[guarded-attr] deliberate lock-free probe
        if not self._commands:
            return
        with self._lock:
            pending = list(self._commands)
            self._commands.clear()
        deferred = []
        for cmd in pending:
            kind, payload = cmd
            if kind == "abort":
                hit = next((c for c in deferred
                            if self._cmd_request_id(c) == payload), None)
                if hit is not None:
                    deferred.remove(hit)
                    self._emit_aborted_pending(hit)
                else:
                    self._abort(payload)
                continue
            if (kind == "add" and len(payload.prompt)
                    + payload.max_new_tokens > self.max_len):
                # drain-time backstop for paths that enqueue directly
                # (FT command-queue restore); live submissions are
                # rejected in add_request before they ever queue
                self._reject_too_long(payload)
                continue
            blocked = self.suspended or bool(deferred)
            if not blocked:
                ok = (self._admit(payload) if kind == "add"
                      else self._admit_handoff(payload))
                blocked = not ok
            if blocked:
                deferred.append(cmd)
        if deferred:
            with self._lock:
                self._commands.extendleft(reversed(deferred))

    # ------------------------------------------------------------------
    def step(self) -> int:
        """One engine iteration (macro-step): drain commands, then up to
        ``steps_per_dispatch`` decode steps for all active slots in ONE
        jit dispatch. Returns the number of decode tokens emitted (0 when
        idle) — token-denominated so callers' activity/backlog signals are
        invariant to the dispatch batching. Serialized against
        ``update_params`` so a weight sync never races a decode step over
        the same slots/cache.

        A :meth:`hard_kill` mid-step unwinds here: ``EngineKilledError``
        propagates out of the locked region (releasing ``_step_lock``),
        and the handler models SIGKILL + replacement process — the latch
        and any wedge hook die with the old process, :meth:`crash` wipes
        slots/cache, and the pump loop continues on the reborn engine.
        ``beats`` is the watchdog's liveness signal: bumped outside all
        locks on every return path, so it only goes silent while a step
        is genuinely stuck."""
        try:
            with self._step_lock:
                out = self._step_locked()
        except EngineKilledError:
            self._kill_evt.clear()
            self._prestep_hook = None
            self.crash()
            out = 0
        self.beats += 1
        return out

    def _gather_slot_arrays(self):   # requires: _step_lock
        """Per-slot device inputs for a decode dispatch. Inactive slots
        ride along as zero rows (budget 0 freezes them on device)."""
        B = self.max_slots
        last_tokens = np.zeros((B, 1), np.int32)
        positions = np.zeros((B,), np.int32)
        temps = np.ones((B,), np.float32)
        budgets = np.zeros((B,), np.int32)
        stop_ids = np.full((B, self._stop_width), -1, np.int32)
        for i, s in enumerate(self._slots):
            if s.active:
                last_tokens[i, 0] = s.tokens[-1]
                positions[i] = s.pos - 1  # index of the token we feed
                temps[i] = s.request.temperature
                budgets[i] = min(
                    s.request.max_new_tokens - len(s.new_tokens),
                    self.max_len - s.pos)
                st = list(s.request.stop_tokens)
                stop_ids[i, : len(st)] = st
        return last_tokens, positions, temps, budgets, stop_ids

    def _step_locked(self) -> int:   # requires: _step_lock
        # 1) command processing between engine steps (non-blocking)
        self._drain_commands()
        # test-only wedge point (observability plane): placed AFTER the
        # command drain so _lock is free while a hook blocks — queue_len
        # and has_pending stay readable from other threads during a
        # simulated hang. A real hang would wedge inside the decode
        # dispatch below; the hook models it at a deterministic boundary.
        hook = self._prestep_hook
        if hook is not None:
            hook(self)
        if self._kill_evt.is_set():
            raise EngineKilledError(f"engine hard-killed at step "
                                    f"{self.steps}")
        # 2) one decode macro-step over active slots
        active = [i for i, s in enumerate(self._slots) if s.active]
        self.steps += 1
        if not active:
            return 0
        self.busy_steps += 1
        if self.paged:
            return self._decode_macro_paged(active)
        K = self.steps_per_dispatch
        last_tokens, positions, temps, budgets, stop_ids = \
            self._gather_slot_arrays()
        if K == 1:
            # legacy single-step dispatch (stop/length handled host-side)
            with spans.phase("repro.engine.decode") as ph:
                with self._shard_ctx():
                    toks, lps, self._cache = self._decode_jit(
                        self.params, jnp.asarray(last_tokens), self._cache,
                        jnp.asarray(positions), self._next_key(),
                        jnp.asarray(temps))
                self.decode_dispatches += 1
                toks, lps = np.asarray(toks), np.asarray(lps)
            self.device_wait_s += ph.seconds
            with spans.span("repro.engine.emit"):
                for i in active:
                    if self._slots[i].active:
                        self.decode_tokens += 1
                        self._append_token(i, int(toks[i]), float(lps[i]))
                self._emit_step_progress(active)
            return len(active)
        # device-resident block: the jit consumes one key per inner step
        # (the SAME split-chain schedule as K single-step dispatches, so
        # sampled streams are reproducible across steps_per_dispatch
        # settings) and hands back the advanced chain head
        with spans.phase("repro.engine.decode") as ph:
            with self._shard_ctx():
                toks, lps, emitted, self._cache, self._key, moe = \
                    self._decode_block_jit(
                        self.params, jnp.asarray(last_tokens), self._cache,
                        jnp.asarray(positions), self._key,
                        jnp.asarray(temps), jnp.asarray(stop_ids),
                        jnp.asarray(budgets))
            self.decode_dispatches += 1
            toks = np.asarray(toks)          # [K, B]
            lps = np.asarray(lps)
            emitted = np.asarray(emitted)
            self._count_moe(moe)
        self.device_wait_s += ph.seconds
        n_emitted = 0
        with spans.span("repro.engine.emit"):
            for i in active:
                # each slot's emitted column is a True-prefix;
                # _append_token re-derives the stop/length finish the
                # device masked on
                for k in range(K):
                    if not self._slots[i].active or not emitted[k, i]:
                        break
                    self.decode_tokens += 1
                    n_emitted += 1
                    self._append_token(i, int(toks[k, i]),
                                       float(lps[k, i]))
            self._emit_step_progress(active)
        return n_emitted

    def _count_moe(self, moe: Dict):   # requires: _step_lock
        """Add a decode block's expert counters (empty for a stack
        without experts): held experts touched and assignments here,
        summed over its scanned steps and layers."""
        if moe:
            self.moe_experts_touched += int(moe["touched"])
            self.moe_tokens_here += int(moe["tokens_here"])

    def _decode_macro_paged(self, active: List[int]) -> int:   # requires: _step_lock
        """Paged decode macro-step: only the ACTIVE slots ride the
        dispatch, padded to a power-of-two batch bucket (bounded
        compiles) with trash page tables and budget 0 for padding rows.
        This batch COMPACTION is where the paged throughput win comes
        from — the dense path pays ``max_slots`` attention rows on every
        dispatch regardless of occupancy, while this path pays the
        occupancy bucket. Greedy streams stay byte-identical to the dense
        path because each real row computes the exact dense op sequence
        over its full table width (see ``attention_decode_paged``)."""
        K = self.steps_per_dispatch
        ba = 1
        while ba < len(active):
            ba <<= 1
        last_tokens = np.zeros((ba, 1), np.int32)
        positions = np.zeros((ba,), np.int32)
        temps = np.ones((ba,), np.float32)
        budgets = np.zeros((ba,), np.int32)
        stop_ids = np.full((ba, self._stop_width), -1, np.int32)
        tables = np.full((ba, self._pages_per_slot), self._trash_pid,
                         np.int32)
        for j, i in enumerate(active):
            s = self._slots[i]
            last_tokens[j, 0] = s.tokens[-1]
            positions[j] = s.pos - 1  # index of the token we feed
            temps[j] = s.request.temperature
            budgets[j] = min(s.request.max_new_tokens - len(s.new_tokens),
                             self.max_len - s.pos)
            st = list(s.request.stop_tokens)
            stop_ids[j, : len(st)] = st
            # the device writes KV at positions [pos-1, pos-1+K): mark
            # their pages dirty NOW, before _append_token can finish the
            # slot and release its table to the prefix cache
            tbl = self._tables[i]
            lo = (s.pos - 1) // self.page_size
            hi = min((s.pos - 1 + K) // self.page_size + 1, len(tbl))
            self._dirty.update(tbl[lo:hi])
            tables[j] = self._full_table(i)
        with spans.phase("repro.engine.decode") as ph:
            with self._shard_ctx():
                toks, lps, emitted, self._pool, self._key, moe = \
                    self._decode_block_paged_jit(
                        self.params, jnp.asarray(last_tokens), self._pool,
                        jnp.asarray(tables), jnp.asarray(positions),
                        self._key, jnp.asarray(temps),
                        jnp.asarray(stop_ids), jnp.asarray(budgets))
            self.decode_dispatches += 1
            toks = np.asarray(toks)          # [K, ba]
            lps = np.asarray(lps)
            emitted = np.asarray(emitted)
            self._count_moe(moe)
        self.device_wait_s += ph.seconds
        n_emitted = 0
        with spans.span("repro.engine.emit"):
            for j, i in enumerate(active):
                for k in range(K):
                    if not self._slots[i].active or not emitted[k, j]:
                        break
                    self.decode_tokens += 1
                    n_emitted += 1
                    self._append_token(i, int(toks[k, j]),
                                       float(lps[k, j]))
            self._emit_step_progress(active)
        return n_emitted

    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, float]:
        """Consistent snapshot of the step-granular counters. Callers
        must NOT hold any proxy/runner lock here (it takes ``_step_lock``,
        and the engine calls back into those holders' locks from under
        it — see the module docstring's cross-class ordering note)."""
        with self._step_lock:
            out = {
                "steps": self.steps,
                "busy_steps": self.busy_steps,
                "decode_dispatches": self.decode_dispatches,
                "prefill_tokens": self.prefill_tokens,
                "decode_tokens": self.decode_tokens,
                "recomputes": self.recomputes,
                "handoffs_out": self.handoffs_out,
                "handoffs_in": self.handoffs_in,
                "crashes": self.crashes,
                "weight_version": self.weight_version,
                "tp_group": self.tp_group,
                "sharding_drops": self.sharding_drops,
                "sync_bytes": self.sync_bytes,
                "local_syncs": self.local_syncs,
                "store_syncs": self.store_syncs,
                "device_wait_s": self.device_wait_s,
                "moe_experts_touched": self.moe_experts_touched,
                "moe_tokens_here": self.moe_tokens_here,
            }
            with self._lock:   # nested acquisition: canonical order
                out["rejected_too_long"] = self.rejected_too_long
            if self.paged:
                out.update({
                    "shared_prefix_tokens": self.shared_prefix_tokens,
                    "free_pages": self._alloc.free_pages,
                    "page_highwater": self._alloc.highwater,
                    "prefix_cached_pages": self._prefix.cached_pages,
                    "prefix_hits": self._prefix.hits,
                    "prefix_misses": self._prefix.misses,
                })
            return out

    def capture_kv_incremental(self) -> Dict[str, object]:
        """FT capture for paged engines: gather ONLY the pages written
        since the last capture (page-granularity dirty tracking) instead
        of device_get-ing every active slot's full dense row. The
        snapshotter merges the returned pages into its host-side pool
        image and assembles self-contained dense records from it, so the
        on-disk snapshot format is unchanged.

        Returns ``pages`` ({pid: [one host array per pool leaf]}),
        ``slots`` (active-slot metadata incl. page table), ``live_pages``
        (pids any restore could still need — slot tables plus prefix
        cache — for pruning the host image), and ``captured_bytes``."""
        with self._step_lock:
            if not self.paged:
                raise RuntimeError("incremental KV capture requires "
                                   "paged=True")
            dirty = sorted(p for p in self._dirty
                           if self._alloc.refcount(p) > 0)
            self._dirty.clear()
            pages: Dict[int, list] = {}
            captured = 0
            if dirty:
                idx = jnp.asarray(dirty, jnp.int32)
                host = jax.device_get(
                    jax.tree.map(lambda leaf: leaf[:, idx], self._pool))
                flat = jax.tree.leaves(host)
                captured = sum(int(a.nbytes) for a in flat)
                for j, pid in enumerate(dirty):
                    pages[pid] = [a[:, j] for a in flat]
            slots = []
            live = set(self._prefix.page_ids())
            for i, s in enumerate(self._slots):
                if not s.active:
                    continue
                live.update(self._tables[i])
                slots.append({
                    "slot": i, "request": s.request,
                    "tokens": list(s.tokens),
                    "new_tokens": list(s.new_tokens),
                    "logprobs": list(s.logprobs),
                    "pos": s.pos,
                    "start_version": s.start_version,
                    "weight_version": self.weight_version,
                    "table": list(self._tables[i]),
                })
            return {"pages": pages, "slots": slots, "live_pages": live,
                    "captured_bytes": captured}

    def pop_result(self, request_id: str) -> Optional[GenResult]:
        with self._lock:
            return self._results.pop(request_id, None)

    @property
    def num_active(self) -> int:
        """Racy by design: the proxy reads this under ITS lock, and
        taking ``_step_lock`` here would close the cross-class deadlock
        cycle described in the module docstring. Occupancy is advisory
        (load balancing) so a stale read is harmless."""
        # analysis: ignore[guarded-attr] lock-free read, see docstring
        return sum(s.active for s in self._slots)

    @property
    def inflight_decode_tokens(self) -> int:
        """Decode tokens held by in-flight slots — the work destroyed if
        this engine dies right now (fault-tolerance accounting). Same
        deliberate lock-free read as ``num_active``."""
        # analysis: ignore[guarded-attr] lock-free read, see num_active
        return sum(len(s.new_tokens) for s in self._slots if s.active)

    @property
    def queue_len(self) -> int:
        with self._lock:
            return len(self._commands)

    @property
    def has_pending(self) -> bool:
        return self.queue_len > 0 or self.num_active > 0

    def run_until_idle(self, max_steps: int = 100000):
        for _ in range(max_steps):
            if not self.has_pending:
                return
            self.step()
        raise RuntimeError("engine did not drain")
