"""Rollout-as-a-Service: the multi-tenant serving tier that owns the
data-plane dispatch loop (ProRL-Agent-style rollout jobs as a service
boundary; ROADMAP item 1).

Before this tier existed, ``LiveRLRunner`` drove ``LLMProxy.pump()``
directly from a private worker loop and was therefore the only possible
client of the disaggregated data plane. :class:`RolloutService` lifts that
loop out: tenants register with a weight and optional in-flight cap,
submit :class:`RolloutJob`\\ s (prompt completions or full env-group
rollouts) and get back a :class:`JobTicket` whose
:class:`~repro.serve.stream.TokenStream` delivers tokens incrementally as
the engines emit them. The trainer is tenant #0 — it reaches the engines
through exactly the same admission path an external client uses.

Scheduling is stride-based weighted fair queueing: each tenant carries a
virtual time that advances by ``1 / weight`` per admitted job, and
admission always picks the eligible tenant with the smallest virtual
time — so under overload the measured share of admitted work tracks the
configured weights (benchmarks/traffic_gen.py measures this). Eligibility
= queued work (or a pull ``source`` that yields a job), in-flight below
the tenant's ``max_inflight``; the service-wide ``max_inflight`` bounds
the total admission window so overload queues at the service, where the
stride scheduler arbitrates, instead of draining unchecked into the
engine FIFO. A full per-tenant queue rejects at submit time
(backpressure, ``JobState.REJECTED``).

Locking (machine-checked by ``python -m repro.analysis``):

- ``_lock`` (RLock) is the SERVICE lock — the role the runner's old pump
  lock played. The service worker holds it for each tick; the trainer
  holds it across the suspend -> update -> resume weight-sync barrier
  (:meth:`barrier`); every public entry point takes it. It is reentrant
  so barrier-context callers (the FT snapshot hook) can re-enter drain
  methods.
- The hand-off gives ``_lock`` to a waiting barrier. :meth:`barrier`
  counts itself in ``_barriers`` (under ``_barrier_lock``, which only
  barrier threads take; ``_announced`` is set while the count is above
  zero) before it blocks on ``_lock``, and out once it has let ``_lock``
  go. Between ticks, with ``_lock`` released, the service thread parks
  while ``_announced`` is set (``_parked`` set meanwhile), looking again
  every ``idle_sleep``, so a barrier waits at most for the tick in
  flight instead of racing the thread's immediate re-acquire. A barrier
  gives a running thread up to 50 ms to park first (the tick in flight,
  or an idle sleep), so the thread parks for every barrier; a barrier's
  exit wakes nothing and never waits for the thread. No barrier, no
  park: serving-only planes and the cooperative sync modes never engage
  it.
- ``_completed_lock`` guards every tenant's ``completed`` list — the one
  structure written from engine callback context (EnvManager
  ``on_complete`` fires under an engine's ``_step_lock`` during pump).
- **Acquisition order: ``_lock`` -> engine ``_step_lock`` -> proxy
  ``_lock`` -> ``TokenStream._cv`` / ``_completed_lock`` /
  ``_barrier_lock`` (leaves).** ``_barrier_lock`` is taken under
  ``_lock`` only by a barrier nested in another.
  The service lock is strictly the outermost lock of the data plane:
  pump() is only ever called with ``_lock`` held, and nothing called
  from under an engine or proxy lock ever takes ``_lock`` (the stream
  push and completion hooks touch only leaf locks). This extends the
  engine/proxy order documented in ``repro.rl.engine`` without creating
  a cycle.
- Tenant bookkeeping (queues, in-flight counts, stride clocks, stats)
  belongs to the service-lock domain: it is only mutated from under
  ``_lock`` or from engine hooks that run inside a pump — which itself
  runs under ``_lock`` — so a single lock covers both paths. The
  ``FailureInjector`` mutates tenant state lock-free from its documented
  quiescent barrier (see ``repro.ft.failure``), exactly as it did against
  the runner's pump-lock domain.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from repro.core.envmanager import EMState, EnvManager, RolloutPolicy
from repro.core.proxy import LLMProxy
from repro.obs import spans
from repro.rl.engine import GenRequest, GenResult
from repro.serve.stream import TokenStream


class JobState:
    QUEUED = "queued"
    RUNNING = "running"
    DONE = "done"
    ABORTED = "aborted"
    REJECTED = "rejected"


@dataclass
class RolloutJob:
    """One unit of serving work.

    ``kind="prompt"``: a single completion — ``prompt`` tokens in,
    streamed tokens out (the external-client fast path).
    ``kind="env"``: a GRPO env group — ``envs`` (pre-built environment
    instances) each driven by an EnvManager under ``policy``; the job is
    done when every manager completes. ``seeds`` (parallel to ``envs``)
    seeds each manager's reset.
    """
    kind: str = "prompt"
    tag: str = "default"               # task/domain tag (affinity routing)
    # prompt jobs
    prompt: Optional[List[int]] = None
    max_new_tokens: int = 32
    temperature: float = 1.0
    stop_tokens: tuple = (2,)
    # env jobs
    envs: List = field(default_factory=list)
    seeds: List[Optional[int]] = field(default_factory=list)
    group_id: str = ""
    policy: Optional[RolloutPolicy] = None
    version: int = 0                   # start weight version (env jobs)
    stream: bool = True                # attach a TokenStream


class JobTicket:
    """Handle returned by :meth:`RolloutService.submit`: job state, the
    incremental token stream, and the final :class:`GenResult` list
    (prompt jobs). Env-job trajectories flow through the tenant's reward
    pipeline into its ``sink`` — the ticket tracks completion only."""

    def __init__(self, job_id: str, tenant: str, job: RolloutJob):
        self.job_id = job_id
        self.tenant = tenant
        self.job = job
        self.state = JobState.QUEUED
        self.stream: Optional[TokenStream] = \
            TokenStream(job_id) if job.stream else None
        self.results: List[GenResult] = []
        self.t_submit = time.monotonic()
        self.t_admit: Optional[float] = None
        self.t_done: Optional[float] = None
        self._remaining = 0            # env jobs: managers still running
        self._done_evt = threading.Event()

    @property
    def done(self) -> bool:
        return self._done_evt.is_set()

    def wait(self, timeout: Optional[float] = None) -> str:
        """Block until the job reaches a terminal state; returns it."""
        if not self._done_evt.wait(timeout=timeout):
            raise TimeoutError(f"job {self.job_id} not done in {timeout}s")
        return self.state

    def _finish(self, state: str):
        self.state = state
        self.t_done = time.monotonic()
        if self.stream is not None:
            self.stream.close("stop" if state == JobState.DONE else state)
        self._done_evt.set()


@dataclass
class Tenant:
    """Per-tenant serving state. All fields except ``completed`` belong
    to the service-lock domain (see module docstring); ``completed`` is
    guarded by the service's ``_completed_lock``."""
    name: str
    weight: float = 1.0
    max_inflight: Optional[int] = None   # None = uncapped (the trainer)
    max_queue: Optional[int] = None      # None = unbounded queue
    tokenizer: object = None             # env jobs: obs/action codec
    sink: Optional[Callable] = None      # scored Trajectory consumer
    source: Optional[Callable] = None    # pull-based job generator
    pre_tick: Optional[Callable] = None  # before admission (staleness)
    post_tick: Optional[Callable] = None  # after drain (surplus cancel)
    observe: Optional[Callable] = None   # affinity profiler hook
    version_fn: Optional[Callable[[], int]] = None
    # reward pipeline (env jobs; None = sink directly, e.g. load tests)
    reward_url: Optional[str] = None
    serverless: object = None
    use_async_reward: bool = True
    reward_retry_limit: int = 2
    # runtime state
    queue: collections.deque = field(default_factory=collections.deque)
    active: List[EnvManager] = field(default_factory=list)
    completed: List[EnvManager] = field(default_factory=list)
    pending_rewards: collections.deque = field(
        default_factory=collections.deque)
    jobs: Dict[str, JobTicket] = field(default_factory=dict)
    inflight: int = 0
    vtime: float = 0.0                   # stride-scheduler virtual time
    stats: Dict[str, int] = field(default_factory=lambda: collections.Counter(
        submitted=0, rejected=0, admitted=0, completed=0, aborted=0,
        failed=0, scored=0, stream_tokens=0, tokens_out=0,
        reward_retries=0, aborted_tokens=0))


class RolloutService:
    """The serving tier: owns ``LLMProxy.pump()``, the EnvManager
    completion cascade, and the serverless reward drain for every tenant.

    Lifecycle mirrors the runner's old worker: :meth:`start` spins up (or
    resumes) the background service thread, :meth:`pause` parks it and
    returns only once no tick is in flight, :meth:`close` is idempotent
    and exception-safe (double-close and close-after-crash both return
    promptly). Synchronous callers can drive :meth:`tick` cooperatively
    instead of starting the thread.
    """

    def __init__(self, proxy: LLMProxy, idle_sleep: float = 0.002,
                 max_pump_steps: int = 200000,
                 max_inflight: Optional[int] = None):
        self.proxy = proxy
        self.idle_sleep = idle_sleep
        self.max_pump_steps = max_pump_steps
        # global admission window (jobs in flight across ALL tenants).
        # Weighted fairness needs contention at the admission point: with
        # an unbounded window every arrival is admitted straight into the
        # engine FIFO and the stride scheduler never arbitrates. Size it
        # to engine capacity (~sum of slots) for serving deployments;
        # None (the trainer default) keeps the old unbounded behavior.
        self.max_inflight = max_inflight
        # service lock: see module docstring. RLock so barrier-context
        # callers (FT snapshot hook) may re-enter drain entry points.
        self._lock = threading.RLock()
        self._completed_lock = threading.Lock()
        self._tenants: Dict[str, Tenant] = {}      # guarded by: _lock
        self._job_counter = itertools.count()      # guarded by: _lock
        # cumulative seconds holding the lock in ticks and sync drains
        # (``repro.service.tick``), the part of them not spent waiting on
        # the engines' dispatches (their ``device_wait_s``), and waiting
        # for the lock in barriers (``repro.runner.lock_wait``); the last
        # barrier's wait is read by its caller while it holds the lock
        self.tick_s = 0.0                          # guarded by: _lock
        self.tick_host_s = 0.0                     # guarded by: _lock
        self.barrier_wait_s = 0.0                  # guarded by: _lock
        self.last_barrier_wait_s = 0.0             # guarded by: _lock
        # the hand-off (module docstring): barriers announced and not yet
        # done with ``_lock``, whether there are any and whether the
        # service thread is parked for them, and its parks
        # (``repro.service.handoff``) and their seconds
        self._barrier_lock = threading.Lock()
        self._barriers = 0                         # guarded by: _barrier_lock
        self._announced = threading.Event()
        self._parked = threading.Event()
        self.handoffs = 0                          # guarded by: _lock
        self.handoff_s = 0.0                       # guarded by: _lock
        self._run = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # liveness beat for the observability watchdog: bumped after
        # every tick OUTSIDE the service lock (bare counter, atomic
        # under the GIL) — readable while a wedged tick holds _lock
        self.beats = 0
        # set by the service thread on crash; surfaced by clients
        # (Runner._await_batch) — written without _lock by design, like
        # the runner's old _rollout_error
        self.error: Optional[BaseException] = None

    # ------------------------------------------------------------------
    # tenancy
    # ------------------------------------------------------------------
    def register_tenant(self, name: str, **kw) -> Tenant:
        with self._lock:
            if name in self._tenants:
                raise ValueError(f"tenant {name!r} already registered")
            t = Tenant(name=name, **kw)
            if t.weight <= 0:
                raise ValueError(f"tenant weight must be > 0: {t.weight}")
            # join at the max of live virtual times so a newcomer gets its
            # fair share going forward, not a retroactive burst
            if self._tenants:
                t.vtime = max(x.vtime for x in self._tenants.values())
            self._tenants[name] = t
            return t

    def tenant(self, name: str) -> Tenant:
        with self._lock:
            return self._tenants[name]

    def tenants(self) -> List[Tenant]:
        with self._lock:
            return list(self._tenants.values())

    # ------------------------------------------------------------------
    # the request boundary
    # ------------------------------------------------------------------
    def submit(self, tenant: str, job: RolloutJob) -> JobTicket:
        """Enqueue a job; admission happens on a later tick in stride
        order. A full tenant queue rejects immediately (backpressure) —
        the ticket comes back ``REJECTED`` with a closed stream."""
        with self._lock:
            t = self._tenants[tenant]
            t.stats["submitted"] += 1
            ticket = JobTicket(f"{t.name}-j{next(self._job_counter)}",
                               t.name, job)
            if t.max_queue is not None and len(t.queue) >= t.max_queue:
                t.stats["rejected"] += 1
                ticket._finish(JobState.REJECTED)
                return ticket
            t.queue.append(ticket)
            return ticket

    def abort_job(self, ticket: JobTicket):
        """Cancel a job: queued jobs finish ``ABORTED`` immediately;
        running env jobs abort their managers (the abort drains through
        subsequent ticks); running prompt jobs abort their request."""
        with self._lock:
            t = self._tenants[ticket.tenant]
            if ticket.state == JobState.QUEUED:
                if ticket in t.queue:
                    t.queue.remove(ticket)
                t.stats["aborted"] += 1
                ticket._finish(JobState.ABORTED)
                return
            if ticket.state != JobState.RUNNING:
                return
            if ticket.job.kind == "env":
                for em in t.active:
                    if getattr(em, "job_id", None) == ticket.job_id:
                        em.abort()
            else:
                self.proxy.abort(f"{ticket.job_id}.r0")

    # ------------------------------------------------------------------
    # admission (stride-scheduled weighted fair queueing)
    # ------------------------------------------------------------------
    def _eligible(self, t: Tenant, dry: set) -> bool:   # requires: _lock
        if t.name in dry:
            return False
        if t.max_inflight is not None and t.inflight >= t.max_inflight:
            return False
        if t.queue:
            return True
        if t.source is None:
            return False
        job = t.source()
        if job is None:
            dry.add(t.name)
            return False
        t.queue.append(JobTicket(
            f"{t.name}-j{next(self._job_counter)}", t.name, job))
        return True

    def _admit_locked(self, only: Optional[str] = None) -> int:   # requires: _lock
        """Admit queued/pulled jobs in stride order until no tenant is
        eligible. Each admission advances the tenant's virtual time by
        ``1 / weight`` — over any congested interval tenants therefore
        receive admissions proportional to their weights."""
        admitted = 0
        dry: set = set()
        while True:
            if self.max_inflight is not None and \
                    sum(t.inflight for t in self._tenants.values()) \
                    >= self.max_inflight:
                return admitted
            cands = [t for t in self._tenants.values()
                     if (only is None or t.name == only)
                     and self._eligible(t, dry)]
            if not cands:
                return admitted
            t = min(cands, key=lambda x: (x.vtime, x.name))
            self._launch_locked(t, t.queue.popleft())
            t.vtime += 1.0 / t.weight
            admitted += 1

    def _launch_locked(self, t: Tenant, ticket: JobTicket):   # requires: _lock
        job = ticket.job
        ticket.state = JobState.RUNNING
        ticket.t_admit = time.monotonic()
        t.jobs[ticket.job_id] = ticket
        t.inflight += 1
        t.stats["admitted"] += 1
        on_tokens = None
        if ticket.stream is not None:
            on_tokens = self._make_stream_hook(t, ticket)
        if job.kind == "prompt":
            rid = f"{ticket.job_id}.r0"
            self.proxy.submit(
                GenRequest(request_id=rid, prompt=list(job.prompt or []),
                           max_new_tokens=job.max_new_tokens,
                           temperature=job.temperature,
                           stop_tokens=job.stop_tokens, tag=job.tag),
                callback=self._make_prompt_cb(t, ticket, rid),
                on_tokens=on_tokens)
            return
        version = t.version_fn() if t.version_fn is not None else job.version
        ticket._remaining = len(job.envs)
        seeds = job.seeds or [None] * len(job.envs)
        for env, seed in zip(job.envs, seeds):
            em = EnvManager(
                env, self.proxy, tokenizer=t.tokenizer, policy=job.policy,
                tag=job.tag, group_id=job.group_id or ticket.job_id,
                on_complete=self._make_on_complete(t),
                on_tokens=on_tokens)
            em.job_id = ticket.job_id
            t.active.append(em)
            em.start(version=version, seed=seed)
        if not job.envs:
            self._finish_ticket(t, ticket, JobState.DONE)

    def _make_stream_hook(self, t: Tenant, ticket: JobTicket):
        def on_tokens(rid: str, cum_tokens, cum_logprobs,
                      _t=t, _tk=ticket):
            n = _tk.stream.push(rid, cum_tokens, cum_logprobs)
            if n:
                _t.stats["stream_tokens"] += n
        return on_tokens

    def _make_prompt_cb(self, t: Tenant, ticket: JobTicket, rid: str):
        # runs from engine finish-hook context (under that engine's
        # _step_lock, inside a pump — i.e. inside the service lock)
        def cb(res: GenResult, _t=t, _tk=ticket, _rid=rid):
            _tk.results.append(res)
            if _tk.stream is not None and res.finish_reason != "aborted":
                # completeness: the final cumulative push is a no-op when
                # streaming already delivered everything
                _tk.stream.push(_rid, res.tokens, res.logprobs)
            _t.stats["tokens_out"] += len(res.tokens)
            done = res.finish_reason != "aborted"
            _t.stats["completed" if done else "aborted"] += 1
            self._finish_ticket(
                _t, _tk, JobState.DONE if done else JobState.ABORTED)
        return cb

    def _make_on_complete(self, t: Tenant):
        def on_complete(em: EnvManager, _t=t):
            with self._completed_lock:
                _t.completed.append(em)
        return on_complete

    def _finish_ticket(self, t: Tenant, ticket: JobTicket, state: str):
        t.jobs.pop(ticket.job_id, None)
        t.inflight -= 1
        ticket._finish(state)

    # ------------------------------------------------------------------
    # the service tick
    # ------------------------------------------------------------------
    def tick(self) -> int:
        """One serving iteration: per-tenant pre-tick policy (staleness),
        stride admission, ONE proxy pump, completion cascade, reward
        drain, post-tick policy (surplus cancellation). Returns an
        activity count (0 == idle)."""
        with self._lock:
            n = self._tick_locked()
        self.beats += 1
        return n

    def _device_wait_s(self) -> float:
        return sum(h.engine.device_wait_s for h in self.proxy.handles)

    def _count_tick(self, ph: spans.phase, waited0: float):   # requires: _lock
        self.tick_s += ph.seconds
        self.tick_host_s += ph.seconds - (self._device_wait_s() - waited0)

    def _tick_locked(self) -> int:   # requires: _lock
        waited0 = self._device_wait_s()
        with spans.phase("repro.service.tick") as ph:
            for t in self._tenants.values():
                if t.pre_tick is not None:
                    t.pre_tick()
            n = self._admit_locked()
            n += self.proxy.pump()
            n += self._drain_completions_locked()
            for t in self._tenants.values():
                n += self._drain_rewards_locked(t)
                if t.post_tick is not None:
                    t.post_tick()
        self._count_tick(ph, waited0)
        return n

    def admit(self, only: Optional[str] = None) -> int:
        with self._lock:
            return self._admit_locked(only)

    def drain_completions(self) -> int:
        with self._lock:
            return self._drain_completions_locked()

    def drain_rewards(self, block: bool = False) -> int:
        with self._lock:
            return sum(self._drain_rewards_locked(t, block=block)
                       for t in self._tenants.values())

    def _drain_completions_locked(self) -> int:   # requires: _lock
        n = 0
        for t in self._tenants.values():
            with self._completed_lock:
                done = list(t.completed)
                t.completed.clear()
            for em in done:
                self._score_locked(t, em)
                if em in t.active:
                    t.active.remove(em)
                ticket = t.jobs.get(getattr(em, "job_id", None))
                if ticket is not None:
                    ticket._remaining -= 1
                    if ticket._remaining <= 0:
                        t.stats["completed"] += 1
                        self._finish_ticket(t, ticket, JobState.DONE)
            n += len(done)
        return n

    def _score_locked(self, t: Tenant, em: EnvManager):   # requires: _lock
        """Reward stage (was LiveRLRunner._score_and_buffer). Async
        tenants submit the serverless call and return immediately — the
        trajectory reaches the sink when its future resolves
        (:meth:`_drain_rewards_locked`)."""
        traj = em.trajectory()
        if t.observe is not None and em.turns:
            t.observe(em)
        if em.state == EMState.FAILED:
            t.stats["failed"] += 1
            return
        if em.state == EMState.ABORTED:
            # redundancy / staleness control absorb these; what they had
            # generated, the in-flight request's part included, is waste
            t.stats["aborted"] += 1
            t.stats["aborted_tokens"] += (sum(traj.loss_mask)
                                          + em.dropped_tokens)
            return
        if t.reward_url is None:
            t.stats["scored"] += 1
            t.stats["tokens_out"] += sum(traj.loss_mask)
            if t.sink is not None:
                t.sink(traj)
            return
        payload = {
            "env_return": em.env_return,
            "tokens": traj.tokens,
            "loss_mask": traj.loss_mask,
            "num_tokens": len(traj.tokens),
            "text": t.tokenizer.decode(traj.tokens),
        }
        if t.use_async_reward:
            # analysis: ignore[blocking-under-lock] pool.submit only: the
            # call executes on the serverless pool thread, not here
            fut = t.serverless.invoke_async(t.reward_url, payload)
            t.pending_rewards.append([traj, payload, fut, 0])
        else:
            # analysis: ignore[blocking-under-lock] sync baseline BY
            # DESIGN: "sync" mode scores rewards inline in the tick (no
            # service thread exists in sync modes, so nothing is
            # serialized behind the lock)
            traj.reward = float(t.serverless.invoke(t.reward_url, payload))
            t.stats["scored"] += 1
            t.stats["tokens_out"] += sum(traj.loss_mask)
            if t.sink is not None:
                t.sink(traj)

    def _drain_rewards_locked(self, t: Tenant,
                              block: bool = False) -> int:   # requires: _lock
        """Completed-PREFIX drain in reward SUBMISSION order (batch
        composition must not depend on serverless timing). Lost
        invocations re-submit from the retained payload up to the
        tenant's retry limit (was LiveRLRunner._drain_rewards)."""
        n = 0
        while t.pending_rewards:
            entry = t.pending_rewards[0]
            traj, payload, fut, attempts = entry
            if not block and not fut.done():
                break
            try:
                traj.reward = float(fut.result())
            except Exception:
                if attempts >= t.reward_retry_limit:
                    raise
                # analysis: ignore[blocking-under-lock] pool.submit only
                entry[2] = t.serverless.invoke_async(t.reward_url, payload)
                entry[3] = attempts + 1
                t.stats["reward_retries"] += 1
                if not block:
                    break
                continue
            t.pending_rewards.popleft()
            t.stats["scored"] += 1
            t.stats["tokens_out"] += sum(traj.loss_mask)
            if t.sink is not None:
                t.sink(traj)
            n += 1
        return n

    def drain_tenant(self, name: str, abort: bool = True):
        """Synchronously drain one tenant's in-flight work (the sync
        baselines' between-iteration barrier: abort leftovers, pump until
        the proxy is idle, block on pending rewards)."""
        with self._lock:
            waited0 = self._device_wait_s()
            with spans.phase("repro.service.tick") as ph:
                t = self._tenants[name]
                if abort:
                    for em in list(t.active):
                        em.abort()
                pumps = 0
                while self.proxy.busy:
                    self.proxy.pump()
                    self._drain_completions_locked()
                    self._drain_rewards_locked(t)
                    pumps += 1
                    if pumps > self.max_pump_steps:
                        raise RuntimeError("rollout did not drain")
                self._drain_completions_locked()
                self._drain_rewards_locked(t, block=True)
            self._count_tick(ph, waited0)

    # ------------------------------------------------------------------
    # weight-sync barrier
    # ------------------------------------------------------------------
    @contextlib.contextmanager
    def barrier(self):
        """The suspend -> update -> resume critical section: holding it
        excludes the service tick, so a weight swap never races a decode
        step (the runner's old pump-lock contract, now a service API).
        The wait for the lock is the ``repro.runner.lock_wait`` span; its
        seconds are ``last_barrier_wait_s`` while the caller holds the
        lock, and add to ``barrier_wait_s``. The barrier announces itself
        first, inside that span, so the service thread yields the lock
        after the tick in flight (:meth:`_yield_to_barriers`)."""
        waited = spans.phase("repro.runner.lock_wait").start()
        with self._barrier_lock:
            self._barriers += 1
            self._announced.set()
        if self._run.is_set():
            # bounded, as a thread that crashes or closes never parks
            self._parked.wait(timeout=0.05)
        try:
            with self._lock:
                waited.stop()
                self.last_barrier_wait_s = waited.seconds
                self.barrier_wait_s += waited.seconds
                yield self
        finally:
            with self._barrier_lock:
                self._barriers -= 1
                if not self._barriers:
                    self._announced.clear()

    def _yield_to_barriers(self) -> Optional[spans.phase]:
        """The service thread, between ticks with ``_lock`` released:
        while a barrier is announced, park until every announced barrier
        has held the lock and let it go, or the service closes. Returns
        the park (span ``repro.service.handoff``), None if no barrier was
        announced."""
        if not self._announced.is_set():
            return None
        with spans.phase("repro.service.handoff") as parked:
            self._parked.set()
            while self._announced.is_set() and \
                    not self._stop.wait(self.idle_sleep):
                pass
            self._parked.clear()
        return parked

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def _loop(self):
        try:
            while not self._stop.is_set():
                if not self._run.wait(timeout=0.05):
                    continue
                parked = self._yield_to_barriers()
                if self._stop.is_set():
                    break
                with self._lock:
                    if parked is not None:
                        self.handoffs += 1
                        self.handoff_s += parked.seconds
                    if not self._run.is_set():
                        continue
                    n = self._tick_locked()
                self.beats += 1
                if n == 0:
                    time.sleep(self.idle_sleep)   # idle: yield the GIL
        except BaseException as e:   # surfaced by clients via self.error
            self.error = e
            self._run.clear()

    def start(self):
        """Start (or resume) the background service thread."""
        if self._stop.is_set():
            raise RuntimeError("service is closed; build a new one")
        # a crashed thread is NOT restarted: self.error stays set and
        # clients surface it (LiveRLRunner._await_batch)
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="rollout-service", daemon=True)
            self._thread.start()
        self._run.set()

    def loop_expected_alive(self) -> bool:
        """Watchdog probe (lock-free bare reads): True while the service
        thread is supposed to be ticking — started, running, not closed,
        and not already crashed loudly (``self.error`` is the loud
        failure path; the watchdog exists for the SILENT one, where the
        thread is wedged inside a tick and beats stop advancing)."""
        return (self._thread is not None and self._run.is_set()
                and not self._stop.is_set() and self.error is None)

    def pause(self):
        """Park the service thread; returns only once no tick is in
        flight (a tick past the flag check finishes first)."""
        self._run.clear()
        with self._lock:
            pass

    def close(self, timeout: float = 10.0):
        """Idempotent, exception-safe shutdown: double-close is a no-op
        and close-after-crash returns promptly (a dead thread joins
        immediately; a wedged one is abandoned after ``timeout`` — it is
        a daemon — instead of hanging the caller)."""
        self._run.clear()
        self._stop.set()
        th, self._thread = self._thread, None
        if th is not None and th.is_alive():
            th.join(timeout=timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # ------------------------------------------------------------------
    def timings(self) -> Dict[str, float]:
        """Service-wide seconds: ``tick_s`` holding the lock in ticks and
        sync drains, ``tick_host_s`` of them not waiting on the chip,
        ``barrier_wait_s`` waiting for the lock in barriers; and the
        service thread's ``handoffs`` (parks for a barrier) and their
        ``handoff_s``."""
        with self._lock:
            return {"tick_s": self.tick_s, "tick_host_s": self.tick_host_s,
                    "barrier_wait_s": self.barrier_wait_s,
                    "handoffs": self.handoffs, "handoff_s": self.handoff_s}

    def stats(self) -> Dict[str, Dict]:
        with self._lock:
            return {t.name: {
                "weight": t.weight, "vtime": round(t.vtime, 3),
                "inflight": t.inflight, "queued": len(t.queue),
                "active_ems": len(t.active),
                "pending_rewards": len(t.pending_rewards),
                **dict(t.stats),
            } for t in self._tenants.values()}
