"""Collector wiring: the bridge between the data plane's ``stats()``
snapshots and the metrics registry.

Two feed paths, chosen per metric:

- **scrape-time collectors** — registered callables the registry runs at
  render time, mapping a component's immutable ``stats()`` snapshot onto
  gauges and (via :meth:`Counter.set_total`) monotone counters. Zero
  hot-path cost: nothing is touched until someone scrapes.
- **event-time observations** — histograms (latency distributions can't
  be reconstructed from totals), fed by the data plane's bare hook
  attributes, which every plane fires OUTSIDE its locks:
  ``proxy.on_ttft`` / ``proxy.on_gap`` (per-request SLO timings from the
  lifecycle records) and ``serverless.on_invoke`` (reward-call wall
  time).

``instrument_runner`` wires the whole training stack (proxy + engines,
buffer, serverless, service tenants, per-step ``StepMetrics`` gauges);
the pieces are also usable à la carte from a serving-only deployment.
Instrument each component at most once per registry.
"""
from __future__ import annotations

from repro.obs.metrics import DEFAULT_BUCKETS, MetricsRegistry

# engine row keys mirrored as monotone counters / point-in-time gauges
# (labels: engine, role). Keys absent from a row — the paged-KV block on
# a dense engine — are skipped.
ENGINE_COUNTERS = (
    ("steps", "engine step() calls"),
    ("busy_steps", "steps that dispatched work"),
    ("decode_dispatches", "decode macro-step dispatches"),
    ("prefill_tokens", "prompt tokens prefetched"),
    ("decode_tokens", "tokens decoded"),
    ("recomputes", "in-flight KV recomputes after weight swaps"),
    ("handoffs_out", "KV handoffs exported (PD prefill side)"),
    ("handoffs_in", "KV handoffs imported (PD decode side)"),
    ("crashes", "engine process crashes (injected or watchdog-killed)"),
    ("sharding_drops", "requests bounced off a mid-resize TP group"),
    ("sync_bytes", "weight-sync bytes pulled"),
    ("local_syncs", "weight versions taken as the trainer's device arrays"),
    ("store_syncs", "weight versions pulled from the weight store"),
    ("rejected_too_long", "requests rejected for context overflow"),
    ("shared_prefix_tokens", "prefill tokens served from shared prefix"),
    ("prefix_hits", "prefix-cache hits"),
    ("prefix_misses", "prefix-cache misses"),
    ("device_wait_s", "seconds from dispatch to readback of prefills and "
                      "decode macro-steps"),
)
ENGINE_GAUGES = (
    ("weight_version", "weight version currently loaded"),
    ("queue_len", "queued requests awaiting a KV slot"),
    ("active_slots", "occupied KV slots"),
    ("max_slots", "KV slot capacity"),
    ("free_pages", "free KV pages (paged engines)"),
    ("page_highwater", "peak KV pages in use (paged engines)"),
    ("prefix_cached_pages", "pages pinned by the prefix cache"),
)
PROXY_COUNTERS = (
    ("requests", "requests submitted"),
    ("aborted", "requests aborted"),
    ("handoffs", "prefill->decode KV handoffs brokered"),
    ("recoveries", "requests re-homed by FT recovery"),
    ("role_switches", "dynamic prefill<->decode role switches"),
    ("switch_migrations", "requests migrated by a role switch"),
)
TENANT_COUNTERS = ("submitted", "rejected", "admitted", "completed",
                   "aborted", "failed", "scored", "stream_tokens",
                   "tokens_out", "reward_retries", "aborted_tokens")
TENANT_GAUGES = ("inflight", "queued", "active_ems", "pending_rewards",
                 "vtime")


def instrument_proxy(reg: MetricsRegistry, proxy) -> None:
    """Engine + proxy counters/gauges (scrape-time, one ``proxy.stats()``
    snapshot per scrape) and the request-level SLO histograms
    (event-time, via the proxy's lifecycle hooks)."""
    eng_c = {k: reg.counter(f"repro_engine_{k}_total", h,
                            ("engine", "role"))
             for k, h in ENGINE_COUNTERS}
    eng_g = {k: reg.gauge(f"repro_engine_{k}", h, ("engine", "role"))
             for k, h in ENGINE_GAUGES}
    beats_g = reg.gauge("repro_engine_beats",
                        "liveness beat (bumped outside all engine locks "
                        "at the end of every step)", ("engine", "role"))
    prox_c = {k: reg.counter(f"repro_proxy_{k}_total", h)
              for k, h in PROXY_COUNTERS}
    routed_g = reg.gauge("repro_proxy_routed_requests",
                         "requests currently routed to an engine")
    pool_g = reg.gauge("repro_proxy_routed_by_pool",
                       "routed requests per engine pool", ("pool",))
    ttft_h = reg.histogram("repro_slo_ttft_seconds",
                           "submit -> first generated token",
                           buckets=DEFAULT_BUCKETS)
    gap_h = reg.histogram("repro_slo_intertoken_seconds",
                          "per-token gap between stream deliveries",
                          buckets=DEFAULT_BUCKETS)
    proxy.on_ttft = lambda s: ttft_h.child().observe(s)
    proxy.on_gap = lambda s: gap_h.child().observe(s)

    def collect():
        st = proxy.stats()
        for row in st["engines"]:
            lab = {"engine": row["name"] or row["pool"],
                   "role": row["role"]}
            for k, fam in eng_c.items():
                if k in row:
                    fam.labels(**lab).set_total(row[k])
            for k, fam in eng_g.items():
                if k in row:
                    fam.labels(**lab).set(row[k])
        for h in proxy.handles:
            beats_g.labels(engine=h.name or h.pool,
                           role=h.role).set(h.engine.beats)
        for k, fam in prox_c.items():
            fam.child().set_total(st[k])
        routed_g.child().set(st["routed_requests"])
        for pool, n in st["routed_by_pool"].items():
            pool_g.labels(pool=pool).set(n)

    reg.register_collector(collect)


def instrument_buffer(reg: MetricsRegistry, buffer) -> None:
    depth = reg.gauge("repro_buffer_depth",
                      "scored trajectories awaiting training")
    version = reg.gauge("repro_buffer_version",
                        "trainer weight version the buffer enforces")
    counters = {
        "total_put": reg.counter("repro_buffer_put_total",
                                 "trajectories accepted"),
        "total_evicted": reg.counter("repro_buffer_evicted_total",
                                     "trajectories evicted as stale"),
        "total_consumed": reg.counter("repro_buffer_consumed_total",
                                      "trajectories handed to the trainer"),
        "total_deduped": reg.counter("repro_buffer_deduped_total",
                                     "replayed trajectories dropped by "
                                     "traj_id dedup"),
        "total_evicted_tokens": reg.counter(
            "repro_buffer_evicted_tokens_total",
            "policy tokens of trajectories evicted as stale"),
    }

    def collect():
        st = buffer.stats()
        depth.child().set(st["depth"])
        version.child().set(st["current_version"])
        for k, fam in counters.items():
            fam.child().set_total(st[k])

    reg.register_collector(collect)


def instrument_serverless(reg: MetricsRegistry, sls) -> None:
    inflight = reg.gauge("repro_serverless_inflight",
                         "invocations currently executing")
    peak = reg.gauge("repro_serverless_peak_instances",
                     "peak concurrent instances")
    counters = {
        "invocations": reg.counter("repro_serverless_invocations_total",
                                   "serverless invocations"),
        "cold_starts": reg.counter("repro_serverless_cold_starts_total",
                                   "cold starts"),
        "failures": reg.counter("repro_serverless_failures_total",
                                "lost invocations (incl. injected)"),
        "payload_bytes": reg.counter("repro_serverless_payload_bytes_total",
                                     "invocation payload bytes"),
    }
    lat_h = reg.histogram("repro_serverless_invoke_latency_seconds",
                          "wall time of one live invocation",
                          buckets=DEFAULT_BUCKETS)
    sls.on_invoke = lambda url, s: lat_h.child().observe(s)

    def collect():
        snap = sls.snapshot()
        inflight.child().set(sls.inflight)
        peak.child().set(snap.peak_instances)
        for k, fam in counters.items():
            fam.child().set_total(getattr(snap, k))

    reg.register_collector(collect)


def instrument_service(reg: MetricsRegistry, svc) -> None:
    """Per-tenant admission/QoS counters and occupancy gauges (labels:
    tenant), the service beat, and the service lock's seconds: held in
    ticks, waited for in weight-sync barriers, handed to them."""
    cnt = {k: reg.counter(f"repro_service_{k}_total",
                          f"tenant {k} events", ("tenant",))
           for k in TENANT_COUNTERS}
    gau = {k: reg.gauge(f"repro_service_{k}",
                        f"tenant {k} (instantaneous)", ("tenant",))
           for k in TENANT_GAUGES}
    beats = reg.gauge("repro_service_beats",
                      "pump-loop liveness beat (bumped after every tick)")
    lock = {"tick_s": reg.counter("repro_service_tick_seconds_total",
                                  "seconds holding the service lock in "
                                  "ticks and sync drains"),
            "tick_host_s": reg.counter(
                "repro_service_tick_host_seconds_total",
                "tick seconds not waiting on the engines' dispatches"),
            "barrier_wait_s": reg.counter(
                "repro_service_barrier_wait_seconds_total",
                "seconds waiting for the service lock in barriers"),
            "handoffs": reg.counter(
                "repro_service_handoffs_total",
                "service-thread parks handing the lock to a barrier"),
            "handoff_s": reg.counter(
                "repro_service_handoff_seconds_total",
                "seconds the service thread parked for barriers")}

    def collect():
        beats.child().set(svc.beats)
        for k, v in svc.timings().items():
            lock[k].child().set_total(v)
        for name, row in svc.stats().items():
            for k, fam in cnt.items():
                fam.labels(tenant=name).set_total(row[k])
            for k, fam in gau.items():
                fam.labels(tenant=name).set(row[k])

    reg.register_collector(collect)


def instrument_runner(reg: MetricsRegistry, runner) -> None:
    """The whole training stack: proxy + engines, buffer, serverless,
    service tenants, and one ``repro_step_<field>`` gauge per
    ``STEP_METRICS_SCHEMA`` entry reflecting the latest completed
    trainer step."""
    from repro.core.scheduler import STEP_METRICS_SCHEMA
    instrument_proxy(reg, runner.proxy)
    instrument_buffer(reg, runner.buffer)
    instrument_serverless(reg, runner.serverless)
    instrument_service(reg, runner.service)
    step_g = {name: reg.gauge(f"repro_step_{name}",
                              f"latest StepMetrics.{name}")
              for name, _ in STEP_METRICS_SCHEMA}

    def collect():
        hist = runner.history
        if not hist:
            return
        for name, val in hist[-1].to_dict().items():
            step_g[name].child().set(val)

    reg.register_collector(collect)
