"""LLMProxy (paper §6.1): the gateway between EnvManagers and inference
workers. Dispatches generation requests at per-trajectory granularity,
routing each request to the hardware class preferred for its task-domain
tag (R1), and forwards ADD/ABORT commands so trajectory admission or
cancellation never stalls ongoing generation. Also implements the
suspend/resume half of the weight-sync protocol (R4).

Prefill/decode disaggregation (§6.3, live counterpart of the simulator's
``pd_disagg`` config): with ``pd_disagg=True`` the proxy runs a two-stage
dispatch — each request's ADD is routed to a prefill-role engine on the
compute-bound pool (H800-class); when that engine emits the request's
:class:`~repro.rl.engine.KVHandoff` (prompt cache + first sampled token),
the proxy migrates it to the least-loaded decode-role engine on the
bandwidth-bound pool (H20-class), where the decode loop runs. ADD/ABORT
and suspend/update/resume semantics are preserved across the handoff: the
route table always points at the engine currently owning the request, and
an abort that races the migration is resolved at handoff time.
"""
from __future__ import annotations

import collections
import threading
import time
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, List, Optional, Tuple

from repro.core.hardware import PERF, REGISTRY, ROLE_CLASS_AFFINITY
from repro.core.resource import Binding, ResourceManager
from repro.rl.engine import (GenRequest, GenResult, InferenceEngine,
                             KVHandoff)


@dataclass
class RequestLifecycle:
    """Per-request data-plane timestamps (``time.monotonic``), recorded
    by the proxy as the single source of truth for latency SLOs —
    submit (``submit()``), admit (first engine progress report),
    first-token (first report with generated tokens), finish (result
    delivery). ``token_times`` holds one ``(t, cum_tokens)`` entry per
    progress arrival that grew the stream, so per-token inter-token gaps
    are derivable without client-side chunk reconstruction."""
    request_id: str
    t_submit: float
    t_admit: Optional[float] = None
    t_first_token: Optional[float] = None
    t_finish: Optional[float] = None
    tokens: int = 0
    finish_reason: str = ""
    token_times: List[Tuple[float, int]] = field(default_factory=list)

    @property
    def ttft(self) -> Optional[float]:
        if self.t_first_token is None:
            return None
        return self.t_first_token - self.t_submit

    @property
    def total(self) -> Optional[float]:
        if self.t_finish is None:
            return None
        return self.t_finish - self.t_submit

    def gaps(self) -> List[float]:
        """Per-token inter-token gaps: each progress arrival's elapsed
        time divided by the tokens it delivered (a K-token macro-step
        counts as K tokens over one arrival gap)."""
        out = []
        for (t0, n0), (t1, n1) in zip(self.token_times,
                                      self.token_times[1:]):
            if n1 > n0:
                out.append((t1 - t0) / (n1 - n0))
        return out

    def snapshot(self) -> "RequestLifecycle":
        return replace(self, token_times=list(self.token_times))


@dataclass
class EngineHandle:
    engine: InferenceEngine
    pool: str                   # hardware pool name ("H800"/"H20"/...)
    name: str = ""
    binding: Optional[Binding] = None   # device group held via the
    #                                     ResourceManager (None = unmanaged)

    def load(self) -> int:
        return self.engine.num_active + self.engine.queue_len

    @property
    def role(self) -> str:
        return self.engine.role


@dataclass
class RebalancerConfig:
    """Hysteresis band for the dynamic prefill<->decode role switch. The
    proxy tracks the decode/prefill queue-depth ratio each pump; only after
    ``window`` consecutive pumps outside [low, high] — and at least
    ``cooldown`` pumps since the last switch — does an engine flip roles,
    so transient bursts never thrash the placement.

    The ratio itself is dispatch-invariant (request-denominated), but the
    DYNAMICS are not: with ``steps_per_dispatch=K`` a decode engine drains
    up to K tokens per slot per pump, so a backlog that K=1 would let
    accumulate across ``window`` pumps may clear within one. That is the
    intended effect of the macro-step — more decode throughput means less
    need to switch — but deployments that want aggressive rebalancing on
    small workloads should lower ``high``/``window`` (or the engines'
    ``steps_per_dispatch``) accordingly."""
    high: float = 4.0        # decode backlog dominates: prefill -> decode
    low: float = 0.25        # prefill backlog dominates: decode -> prefill
    window: int = 4          # consecutive out-of-band pumps required
    cooldown: int = 16       # min pumps between two switches


class LLMProxy:
    def __init__(self, handles: List[EngineHandle],
                 hw_affinity: Optional[Dict[str, str]] = None,
                 pd_disagg: bool = False,
                 resource_manager: Optional[ResourceManager] = None,
                 rebalancer: Optional[RebalancerConfig] = None):
        """hw_affinity: task tag -> pool name, must include "default".

        With ``pd_disagg=True`` the handle list must contain at least one
        ``role="prefill"`` and one ``role="decode"`` engine (all built from
        the same model with the same ``max_len`` so cache slots are
        shape-compatible across the handoff). ``resource_manager`` lets the
        proxy release/re-bind device groups when the dynamic ``rebalancer``
        (PD mode only) switches an engine's role.
        """
        if not handles:
            raise ValueError("LLMProxy needs at least one engine")
        if rebalancer is not None and not pd_disagg:
            raise ValueError("the dynamic rebalancer switches prefill<->"
                             "decode roles and requires pd_disagg=True")
        self.handles = handles
        self.pd_disagg = pd_disagg
        self.rm = resource_manager
        self.rebalancer = rebalancer
        if pd_disagg:
            pre = [h for h in handles if h.role == "prefill"]
            dec = [h for h in handles if h.role == "decode"]
            if not pre or not dec:
                raise ValueError("pd_disagg=True needs at least one "
                                 "prefill-role and one decode-role engine")
            lens = {h.engine.max_len for h in handles}
            if len(lens) != 1:
                raise ValueError(f"PD pools must share max_len, got {lens}")
        self._refresh_roles()
        default_pool = (self.prefill_handles[0].pool if pd_disagg
                        else handles[0].pool)
        self.hw_affinity = dict(hw_affinity or {"default": default_pool})
        self.hw_affinity.setdefault("default", default_pool)
        self._route: Dict[str, EngineHandle] = {}        # guarded by: _lock
        self._callbacks: Dict[str, Callable[[GenResult], None]] = {}  # guarded by: _lock
        self._abort_requested: set = set()               # guarded by: _lock
        # streaming-token subscribers, keyed by request id (Rollout-as-a-
        # Service tier). Keyed on the REQUEST, not the engine, so a stream
        # follows its trajectory across PD handoffs, role switches, and
        # FT re-injection without re-subscribing.
        self._streams: Dict[str, Callable] = {}          # guarded by: _lock
        # per-request lifecycle timestamps (submit/admit/first-token/
        # finish): live records keyed by request id, finished records
        # moved to a bounded deque consumers drain
        # (drain_completed_lifecycles). Both mutated from submitter and
        # engine-hook threads, hence under the routing lock.
        self._lifecycle: Dict[str, RequestLifecycle] = {}  # guarded by: _lock
        self._completed_lifecycles = collections.deque(maxlen=8192)  # guarded by: _lock
        self._lock = threading.Lock()
        self.suspended = False      # bare flag, atomic under the GIL
        # SLO observation hooks (bare, single-assignment at wiring time):
        # called OUTSIDE every proxy/engine lock with one float —
        # on_ttft(seconds) at first-token, on_gap(seconds-per-token) on
        # each later progress arrival. Wired to the obs-plane histograms
        # by repro.obs.instrument.
        self.on_ttft: Optional[Callable[[float], None]] = None
        self.on_gap: Optional[Callable[[float], None]] = None
        for h in handles:
            h.engine.on_finish = self._make_finish_hook(h)
            h.engine.on_progress = self._make_progress_hook(h)
        # stats (engine hooks bump these from engine threads, so they
        # share the routing lock; rebalancer state below does not — it is
        # touched only by the single pump/control thread)
        self.requests = 0                                # guarded by: _lock
        self.aborted = 0                                 # guarded by: _lock
        self.handoffs = 0                                # guarded by: _lock
        self.recoveries = 0                              # guarded by: _lock
        self.routed_by_pool: Dict[str, int] = {}         # guarded by: _lock
        # rebalancer state/stats
        self.role_switches = 0
        self.switch_migrations = 0     # in-flight KV moved by role switches
        self.switch_log: List[Dict] = []
        self._pumps = 0
        self._last_switch_pump: Optional[int] = None
        self._streak_high = 0
        self._streak_low = 0

    def _refresh_roles(self):
        """Recompute role views after construction or a role switch: the
        prefill/decode handle lists, the pump order (prefill engines step
        first so a handoff produced this pump is injected before the decode
        engines step), and the handoff hooks of prefill engines."""
        self.prefill_handles = [h for h in self.handles
                                if h.role == "prefill"]
        self.decode_handles = [h for h in self.handles if h.role == "decode"]
        if self.pd_disagg:
            for h in self.prefill_handles:
                h.engine.on_handoff = self._make_handoff_hook(h)
            self._pump_order = (self.prefill_handles + self.decode_handles
                                + [h for h in self.handles
                                   if h.role == "colocated"])
        else:
            self._pump_order = list(self.handles)

    # ------------------------------------------------------------------
    def _make_finish_hook(self, handle: EngineHandle):
        def hook(result: GenResult):
            now = time.monotonic()
            with self._lock:
                cb = self._callbacks.pop(result.request_id, None)
                self._route.pop(result.request_id, None)
                self._abort_requested.discard(result.request_id)
                self._streams.pop(result.request_id, None)
                lc = self._lifecycle.pop(result.request_id, None)
                if lc is not None:
                    lc.t_finish = now
                    lc.finish_reason = result.finish_reason
                    self._completed_lifecycles.append(lc)
            if cb:
                cb(result)
        return hook

    def _make_progress_hook(self, handle: EngineHandle):
        """Engine streaming hook: runs under the emitting engine's
        ``_step_lock``, so the subscriber lookup takes ``_lock`` briefly
        and the subscriber itself (a TokenStream push — leaf lock only)
        is invoked OUTSIDE it, preserving the cross-class lock order
        documented in ``repro.rl.engine``.

        Also the lifecycle stamping point: the first progress report is
        the admit stamp, the first report that GREW the stream is the
        first-token stamp. Cumulative delivery makes replays (PD
        handoff, KV recompute, FT re-injection) no-ops here too — a
        report that doesn't grow the stream stamps nothing. The SLO
        hooks fire outside the lock, like the stream subscriber."""
        def hook(rid: str, cum_tokens: List[int], cum_logprobs: List[float]):
            now = time.monotonic()
            ttft_obs = gap_obs = None
            with self._lock:
                fn = self._streams.get(rid)
                lc = self._lifecycle.get(rid)
                if lc is not None:
                    if lc.t_admit is None:
                        lc.t_admit = now
                    n = len(cum_tokens)
                    if n > lc.tokens:
                        if lc.t_first_token is None:
                            lc.t_first_token = now
                            ttft_obs = now - lc.t_submit
                        else:
                            t_prev, n_prev = lc.token_times[-1]
                            gap_obs = (now - t_prev) / (n - n_prev)
                        lc.token_times.append((now, n))
                        lc.tokens = n
            if ttft_obs is not None and self.on_ttft is not None:
                self.on_ttft(ttft_obs)
            if gap_obs is not None and self.on_gap is not None:
                self.on_gap(gap_obs)
            if fn is not None:
                fn(rid, cum_tokens, cum_logprobs)
        return hook

    def _route_handoff(self, handoff: KVHandoff, src_pool: str,
                       weight_version: int) -> bool:
        """Route a prefilled trajectory to the least-loaded decode engine,
        or resolve a raced abort instead of migrating a cancelled
        trajectory. Returns True if the handoff was injected. Shared by the
        prefill handoff hook and the role-switch migration path."""
        rid = handoff.request.request_id
        with self._lock:
            if rid in self._abort_requested:
                cb = self._callbacks.pop(rid, None)
                self._route.pop(rid, None)
                self._abort_requested.discard(rid)
                self._streams.pop(rid, None)
                dst = None
            else:
                dst = min(self.decode_handles, key=lambda h: h.load())
                self._route[rid] = dst
                # enqueue while still holding the proxy lock: a
                # concurrent abort() that observes route=dst must find
                # its ABORT ordered after this INJECT in dst's queue
                handoff.source = src_pool
                dst.engine.inject(handoff)
        if dst is None and cb:
            cb(GenResult(
                request_id=rid, tokens=list(handoff.new_tokens),
                logprobs=list(handoff.logprobs),
                finish_reason="aborted",
                weight_version=weight_version,
                prefill_tokens=len(handoff.request.prompt),
                decode_tokens=0))
        return dst is not None

    def _make_handoff_hook(self, src: EngineHandle):
        def hook(handoff: KVHandoff):
            # migrations are counted in `handoffs` (and per-engine
            # handoffs_in), NOT routed_by_pool, so the latter keeps
            # summing to `requests` in both modes
            if self._route_handoff(handoff, src.pool,
                                   src.engine.weight_version):
                # under the lock: several prefill engines can emit
                # handoffs concurrently, and `+=` outside it loses counts
                with self._lock:
                    self.handoffs += 1
        return hook

    def _select(self, tag: str) -> EngineHandle:
        cands = self.prefill_handles if self.pd_disagg else self.handles
        pool = self.hw_affinity.get(tag, self.hw_affinity["default"])
        matched = [h for h in cands if h.pool == pool]
        if not matched:
            matched = cands                  # fallback: forward progress
        return min(matched, key=lambda h: h.load())

    # ------------------------------------------------------------------
    def submit(self, req: GenRequest,
               callback: Callable[[GenResult], None],
               on_tokens: Optional[Callable] = None):
        """Trajectory-level dispatch (ADD command). ``on_tokens``
        subscribes an incremental token stream — called with
        ``(request_id, cumulative_tokens, cumulative_logprobs)`` as the
        engines emit (see ``InferenceEngine.on_progress``)."""
        h = self._select(req.tag)
        now = time.monotonic()
        with self._lock:
            self._callbacks[req.request_id] = callback
            if on_tokens is not None:
                self._streams[req.request_id] = on_tokens
            self._route[req.request_id] = h
            self._lifecycle[req.request_id] = RequestLifecycle(
                request_id=req.request_id, t_submit=now)
            self.requests += 1
            self.routed_by_pool[h.pool] = \
                self.routed_by_pool.get(h.pool, 0) + 1
        h.engine.add_request(req)

    def abort(self, request_id: str):
        """ABORT command: cancel one trajectory's generation (wherever it
        currently lives — prefill engine, in migration, or decode engine).
        Unknown or already-finished ids are a no-op: they are not counted
        in ``aborted`` (nothing was cancelled) and, in PD mode, must not
        pin an ``_abort_requested`` entry forever."""
        with self._lock:
            h = self._route.get(request_id)
            if h is None:
                return
            self.aborted += 1
            if self.pd_disagg:
                self._abort_requested.add(request_id)
        h.engine.abort(request_id)

    # ------------------------------------------------------------------
    # fault tolerance (repro.ft): recovery dispatch + route inspection
    # ------------------------------------------------------------------
    def requests_on(self, handle: EngineHandle) -> List[str]:
        """Request ids currently routed to ``handle`` (in a slot, queued,
        or mid-migration toward it) — the blast radius of losing that
        engine."""
        with self._lock:
            return [rid for rid, h in self._route.items() if h is handle]

    def routed(self, request_id: str) -> bool:
        """True while the request is live somewhere in the plane."""
        with self._lock:
            return request_id in self._route

    def pending_abort_ids(self) -> set:
        """Request ids with an ABORT pending at the proxy level (the PD
        migration guard). Engine-queued aborts are NOT included — snapshot
        capture reads those from the per-engine command snapshots it takes
        anyway, so the full in-flight-abort set costs one pass instead of
        one engine-queue scan per request."""
        with self._lock:
            return set(self._abort_requested)

    def drop_routes(self, request_ids: List[str]):
        """Forget routes/callbacks for requests lost with a dead engine
        and not recoverable from any snapshot (the callers re-issue them
        as fresh requests, or fail the owning EnvManager)."""
        with self._lock:
            for rid in request_ids:
                self._route.pop(rid, None)
                self._callbacks.pop(rid, None)
                self._abort_requested.discard(rid)
                self._streams.pop(rid, None)
                self._lifecycle.pop(rid, None)

    def reinject(self, handoff: KVHandoff,
                 callback: Optional[Callable[[GenResult], None]] = None,
                 on_tokens: Optional[Callable] = None
                 ) -> EngineHandle:
        """Recovery dispatch: route a snapshotted KVHandoff to the
        least-loaded decode-capable engine and inject it. Re-registers the
        result callback (and the ``on_tokens`` stream subscriber) when
        given (cold restore into a fresh proxy); a live recovery keeps the
        existing registration. A weight-version
        mismatch between the snapshot and the target engine re-prefills
        the cache under the current weights at admission
        (``InferenceEngine._admit_handoff``), so restoring an old snapshot
        into a newer plane stays correct."""
        cands = self.decode_handles if self.pd_disagg else self.handles
        rid = handoff.request.request_id
        now = time.monotonic()
        with self._lock:
            dst = min(cands, key=lambda h: h.load())
            if callback is not None:
                self._callbacks[rid] = callback
            if on_tokens is not None:
                self._streams[rid] = on_tokens
            self._route[rid] = dst
            # a live recovery keeps the original lifecycle (latency is
            # measured from the user's submit); a cold restore into a
            # fresh proxy starts a new record at re-injection time
            if rid not in self._lifecycle:
                self._lifecycle[rid] = RequestLifecycle(
                    request_id=rid, t_submit=now,
                    tokens=len(handoff.new_tokens))
            self.recoveries += 1
            dst.engine.inject(handoff)
        return dst

    # ------------------------------------------------------------------
    # per-request lifecycle records (latency source of truth)
    # ------------------------------------------------------------------
    def lifecycle(self, request_id: str) -> Optional[RequestLifecycle]:
        """Snapshot copy of a LIVE request's lifecycle record (None once
        finished — drain the completed deque instead)."""
        with self._lock:
            lc = self._lifecycle.get(request_id)
            return None if lc is None else lc.snapshot()

    def drain_completed_lifecycles(self) -> List[RequestLifecycle]:
        """Pop every finished lifecycle record (each carries its final
        stamps; records are owned by the caller after the drain). The
        backing deque is bounded, so benchmarks that submit faster than
        they drain lose the OLDEST records, never block the hot path."""
        with self._lock:
            out = list(self._completed_lifecycles)
            self._completed_lifecycles.clear()
        return out

    # ------------------------------------------------------------------
    # weight-sync protocol hooks (steps (2)-(4))
    # ------------------------------------------------------------------
    def suspend(self):
        self.suspended = True
        for h in self.handles:
            h.engine.suspend()

    def resume(self):
        self.suspended = False
        for h in self.handles:
            h.engine.resume()

    def weight_paths(self, params
                     ) -> Tuple[List[EngineHandle], List[EngineHandle]]:
        """The two paths a new version of ``params`` (the trainer's) takes
        to the engines: those that take the arrays on the device as they
        are (no mesh, on the one device every leaf lives on;
        ``InferenceEngine.shares_device``), and the rest — a TP group, an
        engine on another chip — which pull it from the weight store."""
        local: List[EngineHandle] = []
        store: List[EngineHandle] = []
        for h in self.handles:
            (local if h.engine.shares_device(params) else store).append(h)
        return local, store

    def update_all(self, params, version: int, recompute_caches: bool = True,
                   handles: Optional[List[EngineHandle]] = None):
        """Protocol steps (3) update + (5) KV-cache recomputation, on
        ``handles`` (default: every engine). Engines already at
        ``version`` no-op (see ``InferenceEngine.update_params``), so
        pulling an unchanged store version — always true on iteration 0
        — costs nothing instead of re-prefilling every in-flight KV
        cache."""
        for h in self.handles if handles is None else handles:
            h.engine.update_params(params, version,
                                   recompute_caches=recompute_caches)

    def update_all_chunks(self, chunks, version: int,
                          recompute_caches: bool = True,
                          handles: Optional[List[EngineHandle]] = None):
        """Sharded weight sync fan-out: every engine of ``handles``
        (default: all) assembles the new version from the store's
        per-shard chunks straight into its own placement
        (``InferenceEngine.update_from_chunks``) — a TP engine never
        materializes a full unsharded param copy; a single-device engine
        concatenates. Same no-op/recompute semantics as
        :meth:`update_all`."""
        for h in self.handles if handles is None else handles:
            h.engine.update_from_chunks(chunks, version,
                                        recompute_caches=recompute_caches)

    def max_group_size(self) -> int:
        """Largest TP group across engines (1 = all single-device). The
        runner keys its push format off this: >1 selects per-shard
        chunked publication (``weightstore.push_params_sharded``)."""
        return max(h.engine.tp_group for h in self.handles)

    # ------------------------------------------------------------------
    # dynamic rebalancing (prefill<->decode role switch)
    # ------------------------------------------------------------------
    def queue_depth_ratio(self) -> float:
        """Decode-side backlog over prefill-side backlog (+1 smoothing so
        an idle side doesn't divide by zero). Backlog is denominated in
        queued + in-flight REQUESTS (``EngineHandle.load``), never in jit
        dispatches, so the signal is invariant to the engines'
        ``steps_per_dispatch`` macro-step batching — a K=8 decode engine
        reports the same backlog as a K=1 engine serving the same work."""
        pre = sum(h.load() for h in self.prefill_handles)
        dec = sum(h.load() for h in self.decode_handles)
        return (dec + 1.0) / (pre + 1.0)

    def _maybe_rebalance(self):
        rb = self.rebalancer
        ratio = self.queue_depth_ratio()
        self._streak_high = self._streak_high + 1 if ratio >= rb.high else 0
        self._streak_low = self._streak_low + 1 if ratio <= rb.low else 0
        if (self._last_switch_pump is not None
                and self._pumps - self._last_switch_pump < rb.cooldown):
            return
        # a switch must leave at least one engine on each side
        if self._streak_high >= rb.window and len(self.prefill_handles) > 1:
            donor = min(self.prefill_handles, key=lambda h: h.load())
            self.switch_role(donor, "decode")
        elif self._streak_low >= rb.window and len(self.decode_handles) > 1:
            donor = min(self.decode_handles, key=lambda h: h.load())
            self.switch_role(donor, "prefill")

    def switch_role(self, handle: EngineHandle, new_role: str):
        """Flip one engine between prefill and decode roles: drain its
        queued commands and in-flight slots, release and re-bind its device
        group under the new role's hardware affinity (when a
        ResourceManager is attached), and re-dispatch the drained work —
        in-flight KV migrates to the remaining engines of the old role via
        the same KVHandoff path the PD split uses."""
        if not self.pd_disagg:
            raise RuntimeError("role switching requires a PD-disaggregated "
                               "proxy")
        if new_role not in ("prefill", "decode") or handle.role == new_role:
            raise ValueError(f"cannot switch {handle.role} -> {new_role}")
        donors = (self.prefill_handles if handle.role == "prefill"
                  else self.decode_handles)
        if len(donors) <= 1:
            raise ValueError(
                f"cannot switch the last {handle.role}-role engine: the "
                "proxy must keep at least one engine on each side")
        old_role, old_pool = handle.role, handle.pool
        eng = handle.engine
        pending = eng.extract_pending()
        # only a decode-role donor can hold in-flight slots (a prefill
        # engine's slots free the moment its handoff is emitted)
        migrated = eng.drain_active_handoffs()
        eng.set_role(new_role)
        if self.rm is not None and handle.binding is not None:
            b = self.rm.rebind(handle.binding.worker_id, new_role)
            if b is not None:
                handle.binding = b
                handle.pool = b.group.pool
        self._refresh_roles()
        # in-flight KV continues on the remaining old-role engines
        for handoff in migrated:
            if self._route_handoff(handoff, old_pool, eng.weight_version):
                self.switch_migrations += 1
        # queued commands re-enter through the proxy's normal routing
        for kind, payload in pending:
            if kind == "add":
                dst = self._select(payload.tag)
                with self._lock:
                    if payload.request_id in self._route:
                        self._route[payload.request_id] = dst
                dst.engine.add_request(payload)
            elif kind == "inject":
                self._route_handoff(payload, payload.source,
                                    payload.weight_version)
            else:                            # abort: follow current route
                with self._lock:
                    dst = self._route.get(payload)
                if dst is not None:
                    dst.engine.abort(payload)
        self.role_switches += 1
        self._last_switch_pump = self._pumps
        self._streak_high = self._streak_low = 0
        self.switch_log.append({
            "pump": self._pumps, "engine": handle.name,
            "from_role": old_role, "to_role": new_role,
            "from_pool": old_pool, "to_pool": handle.pool,
            "migrated": len(migrated), "requeued": len(pending)})

    # ------------------------------------------------------------------
    def placement_report(self, *, prompt_tokens: int = 512,
                         new_tokens: int = 128) -> List[Dict]:
        """Modeled placement pricing per engine: prefill/decode latency of
        its pool's HardwareSpec under the PerfModel, whether the engine's
        role matches its pool's hardware class (affine), and the pool's
        normalized cost. Pools not in the hardware registry (e.g. "local")
        are reported without pricing."""
        cfg = self.handles[0].engine.model.cfg
        out = []
        for h in self.handles:
            hw = REGISTRY.get(h.pool)
            # a live TP group prices as a GROUP: tp-degree speedup in the
            # PerfModel, group-size multiplier on normalized cost
            tp = h.engine.tp_group
            devices = (tp if tp > 1
                       else (h.binding.group.size if h.binding else 1))
            row = {"name": h.name, "pool": h.pool, "role": h.role,
                   "devices": devices, "tp_group": tp}
            if hw is not None:
                conc = max(h.engine.max_slots, 1)
                row.update({
                    "klass": hw.klass,
                    "affine": ROLE_CLASS_AFFINITY.get(h.role) == hw.klass,
                    "modeled_prefill_s": PERF.prefill_time(
                        cfg, prompt_tokens, hw, tp),
                    "modeled_decode_s": PERF.decode_time(
                        cfg, new_tokens, hw, tp,
                        context=prompt_tokens + new_tokens,
                        concurrency=conc),
                    "norm_cost": hw.norm_cost * row["devices"],
                })
            out.append(row)
        return out

    def release_bindings(self):
        """Return every managed device group to the ResourceManager."""
        if self.rm is None:
            return
        for h in self.handles:
            if h.binding is not None:
                self.rm.release(h.binding.worker_id)
                h.binding = None

    # ------------------------------------------------------------------
    def pump(self) -> int:
        """Advance every engine by one macro-step; returns the number of
        decode tokens emitted across engines (token-denominated activity:
        with ``steps_per_dispatch=K`` one pump can emit up to K tokens per
        active slot from a single dispatch each). In PD mode prefill
        engines step before decode engines so a fresh handoff starts
        decoding in the same pump; afterwards the dynamic rebalancer (if
        configured) checks the queue-depth ratio."""
        n = sum(h.engine.step() for h in self._pump_order)
        self._pumps += 1
        if self.rebalancer is not None and self.pd_disagg:
            self._maybe_rebalance()
        return n

    @property
    def busy(self) -> bool:
        return any(h.engine.has_pending for h in self.handles)

    def stats(self) -> Dict:
        # Engine counters are collected FIRST, outside the routing lock:
        # InferenceEngine.stats() takes its _step_lock, and engines call
        # our finish/handoff hooks (which take _lock) while holding
        # _step_lock — taking _step_lock under _lock here would complete
        # that cycle into a deadlock (see the engine module docstring).
        engines = []
        for h in self.handles:
            row = {"pool": h.pool, "name": h.name, "role": h.role,
                   "steps_per_dispatch": h.engine.steps_per_dispatch,
                   # occupancy/backlog gauges (advisory lock-free reads
                   # plus the _lock-guarded queue length) — what the
                   # obs plane exports per role for the autoscaler
                   "queue_len": h.engine.queue_len,
                   "active_slots": h.engine.num_active,
                   "max_slots": h.engine.max_slots}
            row.update(h.engine.stats())
            engines.append(row)
        with self._lock:
            return {
                "requests": self.requests,
                "aborted": self.aborted,
                "pd_disagg": self.pd_disagg,
                "handoffs": self.handoffs,
                "recoveries": self.recoveries,
                "routed_by_pool": dict(self.routed_by_pool),
                "routed_requests": len(self._route),
                "role_switches": self.role_switches,
                "switch_migrations": self.switch_migrations,
                # snapshot COPIES down to the entry dicts: a scraper
                # mutating (or iterating) its snapshot must never touch
                # the live rebalancer log
                "switch_log": [dict(e) for e in self.switch_log],
                "engines": engines,
            }


def format_placement_row(row: Dict) -> str:
    """One-line rendering of a ``placement_report`` row (launchers)."""
    out = (f"{row['name']:>10} pool={row['pool']:<5} "
           f"role={row['role']:<7}")
    if "affine" in row:
        out += (f" affine={row['affine']} "
                f"prefill_s={row['modeled_prefill_s']:.2e} "
                f"decode_s={row['modeled_decode_s']:.2e} "
                f"cost={row['norm_cost']}")
    return out


def format_switch_event(ev: Dict) -> str:
    """One-line rendering of a ``switch_log`` entry (launchers)."""
    return (f"rebalance@pump{ev['pump']}: {ev['engine']} "
            f"{ev['from_role']}->{ev['to_role']} "
            f"pool {ev['from_pool']}->{ev['to_pool']} "
            f"(migrated {ev['migrated']} in-flight)")


def build_pd_proxy(model, params, *, prefill_pool: str = "H800",
                   decode_pool: str = "H20", n_prefill: int = 1,
                   n_decode: int = 1, max_slots: int = 8,
                   max_len: int = 512, seed: int = 0,
                   hw_affinity: Optional[Dict[str, str]] = None,
                   resource_manager: Optional[ResourceManager] = None,
                   devices_per_engine: int = 1,
                   prefill_devices_per_engine: Optional[int] = None,
                   decode_devices_per_engine: Optional[int] = None,
                   shard_rules: Optional[Dict] = None,
                   rebalancer: Optional[RebalancerConfig] = None,
                   steps_per_dispatch: int = 8,
                   donate: bool = True,
                   paged: bool = False,
                   page_size: int = 16) -> LLMProxy:
    """Build a PD-disaggregated proxy: ``n_prefill`` prefill-role engines on
    the compute pool and ``n_decode`` decode-role engines on the bandwidth
    pool (the live analogue of the simulator's ``gen_pools`` +
    ``pd_disagg=True`` configuration).

    With a ``resource_manager``, each engine acquires a real device group
    through ``ResourceManager.bind_affine`` — prefill engines land on
    compute-class pools, decode engines on bandwidth-class pools, with
    opportunistic fallback when the preferred class is exhausted — and the
    ``prefill_pool``/``decode_pool`` names are superseded by the bound
    pools. Pass a ``RebalancerConfig`` to enable the dynamic
    prefill<->decode role switch (which releases/re-binds those groups).

    ``devices_per_engine`` > 1 makes every engine a LIVE TP group: each
    engine claims a disjoint slice of ``jax.devices()``, builds a
    (1, n) group mesh, and executes sharded over it (see
    ``InferenceEngine`` with ``mesh=``). Prefill and decode sizes can
    differ (``prefill_devices_per_engine`` / ``decode_devices_per_engine``
    override the common value — the §6.3 heterogeneous split, e.g. 2-way
    prefill feeding 4-way decode; KV handoffs re-shard across the size
    change). Whenever ANY group exceeds 1, every engine gets a disjoint
    group (a size-1 group mesh for the others) so no two engines contend
    for the same device. Too few visible devices or a group size that
    shards nothing raises instead of silently degrading to one device —
    the no-op ``devices_per_engine`` trap this replaces. On CPU, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before
    importing jax.

    ``steps_per_dispatch``/``donate`` configure the decode hot path of
    every engine (K scanned decode steps per jit dispatch / in-place
    donated KV caches; see ``InferenceEngine``). The shared ``params``
    pytree is exactly why engines never donate their params argument
    (TP engines place a private SHARDED copy of it per group).

    ``paged=True`` switches EVERY engine of the pool to the paged KV
    plane (shared page pool + prefix cache + compacted decode dispatch;
    see ``InferenceEngine``). The KVHandoff interchange format is
    unchanged, so mixed paged/dense pools also interoperate — but a
    uniform setting keeps capacity accounting comparable across the
    pool."""
    pre_n = (prefill_devices_per_engine
             if prefill_devices_per_engine is not None
             else devices_per_engine)
    dec_n = (decode_devices_per_engine
             if decode_devices_per_engine is not None
             else devices_per_engine)
    if pre_n < 1 or dec_n < 1:
        raise ValueError("devices_per_engine must be >= 1, got "
                         f"prefill={pre_n} decode={dec_n}")
    bound = []

    def _bind(wid, role, n_devices):
        if resource_manager is None:
            return None
        b = resource_manager.bind_affine(wid, role, n_devices=n_devices)
        if b is None:
            for w in bound:                  # no partial-placement leak
                resource_manager.release(w)
            raise RuntimeError(
                f"resource manager cannot bind {wid} ({role}) (snapshot: "
                f"{resource_manager.snapshot()['free']})")
        bound.append(wid)
        return b

    # bind the whole placement BEFORE claiming live devices: an RM
    # inventory shortfall reports as "cannot bind" (with partial release)
    # rather than a live-device error, and a live-device shortfall never
    # leaks RM bindings either
    plan = ([(f"prefill-{i}", "prefill", pre_n, seed + i, prefill_pool)
             for i in range(n_prefill)]
            + [(f"decode-{i}", "decode", dec_n, seed + 1000 + i,
                decode_pool)
               for i in range(n_decode)])
    bindings = [_bind(name, role, n) for name, role, n, _, _ in plan]
    meshes = [None] * len(plan)
    if max(pre_n, dec_n) > 1:
        from repro.launch.mesh import (allocate_engine_devices,
                                       make_group_mesh)
        try:
            groups = allocate_engine_devices([n for _, _, n, _, _ in plan])
        except RuntimeError:
            if resource_manager is not None:
                for w in bound:
                    resource_manager.release(w)
            raise
        meshes = [make_group_mesh(g) for g in groups]
    handles = []
    for (name, role, _, eng_seed, pool), b, mesh in zip(plan, bindings,
                                                        meshes):
        eng = InferenceEngine(model, params, max_slots=max_slots,
                              max_len=max_len, seed=eng_seed, role=role,
                              steps_per_dispatch=steps_per_dispatch,
                              donate=donate, mesh=mesh,
                              shard_rules=shard_rules, paged=paged,
                              page_size=page_size)
        handles.append(EngineHandle(eng, b.group.pool if b else pool,
                                    name, binding=b))
    return LLMProxy(handles, hw_affinity=hw_affinity, pd_disagg=True,
                    resource_manager=resource_manager, rebalancer=rebalancer)
