"""Trace spans of the live RL loop, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: its events share the clock
of the device trace, so a profiler session (``jax.profiler.start_trace``)
shows what each thread of the program was doing while the chip ran or
idled. With no session active a span costs one ``TraceMe`` check, so the
spans are always on: there is no flag.

:class:`phase` is a span that also times itself. Where a span's interval
feeds a ``StepMetrics`` field or a counter, both come from the same two
``time.monotonic()`` stamps, so the trace and the number cannot drift
apart.

Names are ``repro.<layer>.<phase>``, after the layers of ``PERF.md``;
:data:`SPANS` lists every one, by the thread that opens it.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

SPANS = (
    # trainer thread, LiveRLRunner.run_steps: one step of the protocol
    # (StepMetrics.wall_s) and its phases
    "repro.runner.step",
    "repro.runner.fetch",        # (1) batch fetch: fetch_s
    "repro.runner.push_wait",    # last step's weight push awaited, where one
    #                              is in flight: push_wait_s
    "repro.runner.lock_wait",    # acquiring the service lock in
    #                              RolloutService.barrier (every caller):
    #                              lock_wait_s, barrier_wait_s
    "repro.runner.sync",         # (2)-(5) under the lock: sync_s
    "repro.weights.pull",        # an engine placing a version pulled from
    #                              the store on its devices (inside sync)
    "repro.weights.local",       # an engine on the trainer's device taking
    #                              the trainer's arrays as they are (inside
    #                              sync; local_syncs)
    "repro.engine.recompute",    # (5) in-flight KV recompute (inside sync)
    "repro.runner.train",        # (6) pack, GRPO step, loss readback: train_s
    "repro.runner.pack",         # GRPO advantages, padding, upload
    # weight-push thread: flatten_params' device-to-host copy + publish
    "repro.weights.push",
    # rollout thread: one RolloutService tick, or a sync baseline's drain,
    # under the service lock (tick_s, tick_host_s)
    "repro.service.tick",
    "repro.service.handoff",     # parked between ticks for an announced
    #                              barrier (handoffs, handoff_s)
    "repro.engine.prefill",      # admission prefill, dispatch to readback
    "repro.engine.decode",       # decode macro-step, dispatch to readback
    #                              (both: the engine's device_wait_s)
    "repro.engine.emit",         # per-token bookkeeping and streaming
    "repro.env.step",            # an environment step between turns
)


def span(name: str) -> TraceAnnotation:
    """A trace span around a ``with`` block."""
    return TraceAnnotation(name)


class phase:
    """A span that times itself: ``seconds`` is ``t1 - t0``, the
    ``time.monotonic()`` stamps taken just inside the span's ends. Use as
    a context manager, or :meth:`start` / :meth:`stop` where the interval
    ends inside another ``with`` block (a lock's)."""

    __slots__ = ("_span", "t0", "t1")

    def __init__(self, name: str):
        self._span = TraceAnnotation(name)
        self.t0 = self.t1 = 0.0

    def start(self) -> "phase":
        self._span.__enter__()
        self.t0 = time.monotonic()
        return self

    def stop(self) -> None:
        self.t1 = time.monotonic()
        self._span.__exit__(None, None, None)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __enter__(self) -> "phase":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False
