"""Rollout scheduler + asynchronous training orchestration (paper §6).

``LiveRLRunner`` drives the REAL pipeline (tiny models, real environments,
real GRPO updates) through the paper's six-step weight-sync protocol:

  (1) get_batch   — blocking retrieval from SampleBuffer
  (2) suspend     — LLMProxy stops admitting requests (in-flight preserved)
  (3) update      — every engine takes the trainer's version: one on the
                    trainer's own device takes its arrays as they are; any
                    other pulls it from the Mooncake store (a version-
                    matched update is a no-op: nothing re-prefills)
  (4) resume      — pending generation continues
  (5) recomp      — in-flight trajectories' KV caches rebuilt under the new
                    weights (so they continue instead of restarting)
  (6) train_step  — the GRPO update, genuinely overlapped with rollout

Since the Rollout-as-a-Service refactor the runner no longer owns the
dispatch loop: ALL pump/drain work lives in
:class:`repro.serve.RolloutService`, and the runner is simply the
service's first tenant. It contributes a pull-based job ``source``
(:meth:`_next_job` — the backpressure + group-top-up policy), per-tick
policy hooks (staleness enforcement before admission, redundancy
cancellation after the drain), and a ``sink`` (the SampleBuffer). The
trainer therefore reaches the engines through exactly the same admission
path an external serving client uses, and the runner contains NO direct
``proxy.pump()`` call.

The overlap is real, not cooperative: in the asynchronous modes
("rollart", "areal", "one_off") the entire rollout side — proxy pump,
EnvManager completion cascade, serverless reward scoring — runs on the
service's background thread, which keeps producing into ``SampleBuffer``
while the trainer thread executes the six-step protocol. The ONLY barrier
between the two threads is the suspend → update → resume critical
section, taken under the SERVICE lock (:meth:`RolloutService.barrier`,
the role the runner's private pump lock used to play) so a weight swap
never races a decode step. Reward scoring is non-blocking
(``ServerlessPlatform.invoke_async``): a scored trajectory enters the
buffer when its future resolves — drained in submission order so batch
composition stays deterministic — and the weight push after each train
step happens on its own thread, awaited only at the next suspend barrier.
The push runs only while some engine pulls from the store: a plane whose
engines all sit on the trainer's device publishes v0 alone.
``StepMetrics.decode_during_train`` counts decode tokens the engines
generated while ``train_step`` ran (> 0 in the threaded modes, 0 in the
synchronous baselines; see benchmarks/async_overlap.py).

Also implements trajectory-level staleness enforcement (abort EnvManagers
whose start_version < n - alpha, every rollout tick — stricter than AReaL)
and redundant environment rollouts (launch extra groups, cancel the slowest
once the target count is met; exploits GRPO's group structure).

Modes ("rollart", "sync", "sync_plus", "one_off", "areal") reproduce the
paper's baselines with the same code path, differing only in coordination:
  sync      — rollout and training strictly alternate; blocking reward
  sync_plus — sync + async (serverless-offloaded) reward scoring
  one_off   — training consumes the PREVIOUS iteration's batch while the
              next one rolls out (threaded; one-step pipeline)
  areal     — staleness bound applied at trajectory start only (threaded)
  rollart   — bounded staleness alpha enforced per tick + affinity
              (threaded)

Concurrency note: the runner's rollout-side state (``active`` managers,
``_pending_rewards``, ``_completed_this_round``, sampler/seed RNGs) is
ALIASED into its service tenant — the same list/deque objects, never
rebound by either side — and belongs to the service-lock domain
documented in ``repro.serve.service``. The runner's policy hooks run
inside the service tick (lock held); the FT plane mutates the same state
from its documented quiescent barrier (``repro.ft.failure``).
"""
from __future__ import annotations

import itertools
import time
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.core.buffer import SampleBuffer
from repro.core.envmanager import EMState, EnvManager, RolloutPolicy
from repro.core.profiler import AffinityProfiler
from repro.core.proxy import LLMProxy
from repro.core.serverless import ServerlessPlatform
from repro.core.weightstore import (MooncakeStore, pull_param_chunks,
                                    pull_params, push_params,
                                    push_params_sharded)
from repro.data.pipeline import Trajectory, TaskSampler, pack_batch
from repro.data.tokenizer import ByteTokenizer
from repro.envs import make_env
from repro.obs import spans
from repro.rl.trainer import TrainState
from repro.serve.service import RolloutJob, RolloutService

MODES = ("rollart", "sync", "sync_plus", "one_off", "areal")
THREADED_MODES = ("rollart", "areal", "one_off")

# Default multi-task mix: the paper's Fig. 3/5 analysis centers on the
# long-tail SWE/webshop environments, so the live runner schedules them by
# default — weighted toward the fast decode-heavy tasks so batches keep
# filling while the long-tail trajectories mature.
DEFAULT_TASKS = ("math", "game", "swe", "webshop")
DEFAULT_TASK_WEIGHTS = (0.35, 0.35, 0.15, 0.15)


@dataclass
class RunnerConfig:
    batch_size: int = 8
    group_size: int = 4
    alpha: int = 1
    mode: str = "rollart"
    tasks: tuple = DEFAULT_TASKS
    # None = the weighted default mix when `tasks` is DEFAULT_TASKS,
    # uniform otherwise; an explicit tuple must match len(tasks)
    task_weights: Optional[tuple] = None
    redundancy: float = 1.0           # env groups launched / needed
    online_affinity: bool = False     # paper §9: auto-derive hw_mapping
    pd_disagg: bool = False           # §6.3: proxy must be two-stage
    #   (prefill pool -> KV handoff -> decode pool; see
    #   repro.core.proxy.build_pd_proxy for constructing such a proxy)
    # resource plane (launchers: --pools / --affinity). `pools` is the
    # heterogeneous device inventory a ResourceManager is built from;
    # `affinity` binds engines role-affinely through it and enables the
    # dynamic prefill<->decode rebalancer.
    pools: Optional[Dict[str, int]] = None
    affinity: bool = False
    # decode macro-step size: K scanned decode steps per jit dispatch
    # (InferenceEngine.steps_per_dispatch; launchers build the proxy's
    # engines with this). Commands drain between macro-steps, so the
    # runner's ABORT-driven controls — per-tick staleness enforcement and
    # redundancy cancellation — act within at most K decode tokens per
    # slot; lower it when abort latency matters more than throughput.
    steps_per_dispatch: int = 8
    max_new_tokens: int = 32
    temperature: float = 1.0
    reward_url: str = "fc://rollart/reward"
    max_pump_steps: int = 200000
    # backpressure: the worker stops spawning new env groups once the
    # buffer already holds this many batches ahead of the trainer
    max_buffered_batches: int = 2
    batch_timeout_s: float = 300.0    # threaded-mode starvation guard
    # fault tolerance: a reward invocation that dies (ServerlessError —
    # container eviction or an injected fault) is re-submitted from its
    # retained payload up to this many times before the error surfaces
    reward_retry_limit: int = 2
    # weighted-QoS share of the trainer tenant when the RolloutService is
    # shared with external serving tenants (stride scheduling; see
    # repro.serve.service — irrelevant while the trainer is alone)
    tenant_weight: float = 1.0
    seed: int = 0

    def sampler_weights(self) -> Optional[List[float]]:
        if self.task_weights is not None:
            return list(self.task_weights)
        if tuple(self.tasks) == DEFAULT_TASKS:
            return list(DEFAULT_TASK_WEIGHTS)
        return None                   # custom task set: uniform


@dataclass
class StepMetrics:
    step: int
    wall_s: float
    loss: float
    reward_mean: float
    evicted: int                 # evictions during THIS step (delta)
    aborted: int                 # aborts during THIS step (delta)
    trajs: int
    decode_during_train: int = 0     # decode tokens generated while
    #                                  train_step ran (overlap evidence)
    batch_fetched_step: int = 0      # trainer step at which the trained
    #                                  batch left the buffer (-1 = primed
    #                                  before any training; < step in
    #                                  one_off mode: previous-batch rule)
    batch_max_version: int = 0       # newest start_version in the batch
    role_switches: int = 0           # dynamic prefill<->decode role
    #                                  switches during THIS step (delta)
    deduped: int = 0                 # replayed trajectories dropped by the
    #                                  buffer's traj_id dedup (delta; > 0
    #                                  only after a rollout-plane restore)
    fetch_s: float = 0.0             # step (1): blocking batch retrieval
    barrier_s: float = 0.0           # steps (2)-(5): push_wait_s +
    #                                  lock_wait_s + sync_s
    train_s: float = 0.0             # step (6): the GRPO update itself
    staleness: int = 0               # weight-version staleness of the
    #                                  trained batch: trainer version at
    #                                  fetch minus the OLDEST start_version
    #                                  in the batch (worst case)
    push_wait_s: float = 0.0         # barrier: last step's push awaited
    #                                  (0 with no push in flight)
    lock_wait_s: float = 0.0         # barrier: waiting for the service lock
    sync_s: float = 0.0              # barrier, under the lock: suspend,
    #                                  update (device path or store pull)
    #                                  with KV recompute, resume, barrier
    #                                  hook
    rollout_host_s: float = 0.0      # seconds the service ticks held its
    #                                  lock not waiting on the chip (delta
    #                                  of the service's tick_host_s)
    aborted_tokens: int = 0          # policy tokens of trajectories aborted
    #                                  before scoring, the in-flight
    #                                  request's included (delta)
    evicted_tokens: int = 0          # policy tokens of trajectories the
    #                                  buffer evicted as stale (delta)
    # MoE stacks (0 otherwise). Decode, summed over scanned steps and
    # layers (delta of the engines' counters): held experts some emitting
    # row routed to, and the assignments that landed on held experts.
    # Train, from the GRPO step's metrics: assignments on held experts
    # over the layers, and per layer and held expert the largest and the
    # mean number of assignments.
    moe_decode_experts_touched: int = 0
    moe_decode_tokens_here: int = 0
    moe_train_tokens_here: int = 0
    moe_train_load_max: float = 0.0
    moe_train_load_mean: float = 0.0

    def to_dict(self) -> Dict[str, float]:
        """Stable flat schema — key order and types are
        ``STEP_METRICS_SCHEMA``, consumed verbatim by the runner's
        per-step log line and the ``repro_step_*`` gauge exporter
        (``repro.obs.instrument``); regression-tested in
        tests/test_observability.py. Add fields THERE, not ad hoc."""
        return {name: typ(getattr(self, name))
                for name, typ in STEP_METRICS_SCHEMA}


# (field, type) pairs defining the stable StepMetrics export schema; the
# obs plane derives one `repro_step_<field>` gauge per entry.
STEP_METRICS_SCHEMA = (
    ("step", int),
    ("wall_s", float),
    ("fetch_s", float),
    ("barrier_s", float),
    ("train_s", float),
    ("loss", float),
    ("reward_mean", float),
    ("evicted", int),
    ("aborted", int),
    ("trajs", int),
    ("decode_during_train", int),
    ("batch_fetched_step", int),
    ("batch_max_version", int),
    ("staleness", int),
    ("role_switches", int),
    ("deduped", int),
    ("push_wait_s", float),
    ("lock_wait_s", float),
    ("sync_s", float),
    ("rollout_host_s", float),
    ("aborted_tokens", int),
    ("evicted_tokens", int),
    ("moe_decode_experts_touched", int),
    ("moe_decode_tokens_here", int),
    ("moe_train_tokens_here", int),
    ("moe_train_load_max", float),
    ("moe_train_load_mean", float),
)


TRAINER_TENANT = "trainer"


class LiveRLRunner:
    """Producer/consumer runner of the full RollArt pipeline — tenant #0
    of a :class:`~repro.serve.RolloutService`.

    Asynchronous modes run the rollout side on the service's background
    thread; synchronous baselines tick the same service cooperatively on
    the trainer thread. Call :meth:`close` (or use as a context manager)
    to shut the service and the push thread down — close is idempotent
    and exception-safe (double-close / close-after-crash return promptly).
    """

    def __init__(self, cfg: RunnerConfig, proxy: LLMProxy,
                 train_state: TrainState,
                 train_step_fn: Callable,
                 serverless: ServerlessPlatform,
                 reward_fn: Callable[[Dict], float],
                 store: Optional[MooncakeStore] = None,
                 seq_len: int = 512,
                 service: Optional[RolloutService] = None):
        self.cfg = cfg
        assert cfg.mode in MODES
        if cfg.pd_disagg and not proxy.pd_disagg:
            raise ValueError("RunnerConfig.pd_disagg=True requires a "
                             "PD-disaggregated LLMProxy (build_pd_proxy)")
        if cfg.affinity and (proxy.rm is None or proxy.rebalancer is None):
            raise ValueError(
                "RunnerConfig.affinity=True requires a proxy built with a "
                "ResourceManager and a RebalancerConfig (build_pd_proxy("
                "resource_manager=..., rebalancer=...))")
        self.proxy = proxy
        self.state = train_state
        self.train_step_fn = train_step_fn
        self.serverless = serverless
        self.serverless.deploy(cfg.reward_url, reward_fn)
        self.store = store or MooncakeStore(bucket_mb=1)
        self.buffer = SampleBuffer(alpha=cfg.alpha)
        self.tok = ByteTokenizer()
        self.sampler = TaskSampler(list(cfg.tasks), seed=cfg.seed,
                                   weights=cfg.sampler_weights())
        self.seq_len = seq_len
        self.version = 0
        self.profiler = AffinityProfiler() if cfg.online_affinity else None
        self._seed_counter = itertools.count(cfg.seed * 1000)
        self.history: List[StepMetrics] = []
        self.threaded = cfg.mode in THREADED_MODES
        # async modes score rewards through invoke_async + a pending-
        # futures drain; plain "sync" keeps the blocking inline call
        self._use_async_reward = cfg.mode != "sync"
        # --- the serving tier -----------------------------------------
        # An externally supplied service lets the trainer share the data
        # plane with serving tenants (launch/serve.py --service); by
        # default the runner builds a private one.
        self.service = service if service is not None else RolloutService(
            proxy, max_pump_steps=cfg.max_pump_steps)
        self._tenant = self.service.register_tenant(
            TRAINER_TENANT,
            weight=cfg.tenant_weight,
            tokenizer=self.tok,
            sink=self.buffer.put,
            source=self._next_job,
            pre_tick=self._enforce_staleness,
            post_tick=self._post_tick,
            observe=(self._observe_em if self.profiler is not None
                     else None),
            version_fn=lambda: self.version,
            reward_url=cfg.reward_url,
            serverless=self.serverless,
            use_async_reward=self._use_async_reward,
            reward_retry_limit=cfg.reward_retry_limit)
        # Aliases into the tenant/service state: the SAME objects, never
        # rebound by either side (the FT plane mutates them in place
        # through the runner under its quiescent barrier)
        self.active: List[EnvManager] = self._tenant.active
        self._pending_rewards = self._tenant.pending_rewards
        self._completed_lock = self.service._completed_lock
        self._completed_this_round = self._tenant.completed
        # fault-tolerance hook: called at the end of every suspend ->
        # update -> resume barrier while the service lock is still held
        # (the rollout plane is quiescent there) — the FT supervisor
        # installs its snapshot capture here (see repro.ft.supervisor)
        self.barrier_hook: Optional[Callable[["LiveRLRunner", int], None]] \
            = None
        # traj_ids trained per step (dedup / parity audits)
        self.trained_log: List[List[str]] = []
        # async weight push: one thread so publications stay ordered
        self._push_pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="weight-push")
        self._push_future: Optional[Future] = None
        self._closed = False
        # one_off pipeline state: the batch fetched last step, trained on
        # this step while its successor rolls out
        self._prev_batch: Optional[List[Trajectory]] = None
        self._prev_batch_fetched_step = -1
        self.last_batch: List[Trajectory] = []
        self._last_evicted = 0
        self._last_aborted = 0
        self._last_role_switches = 0
        self._last_deduped = 0
        self._last_aborted_tokens = 0
        self._last_evicted_tokens = 0
        self._last_tick_host_s = 0.0
        self._last_moe_decode = (0, 0)
        # weight-sync format: a plane with TP engine groups publishes
        # PER-SHARD chunks (engines assemble their own shards and never
        # materialize a full per-engine copy); a single-device plane
        # keeps the dense per-leaf format. Chunk dims follow the same
        # serve rules the engines place with, so chunks and shards line
        # up by construction.
        self._tp_chunks = self.proxy.max_group_size()
        if self._tp_chunks > 1:
            from repro.distributed.sharding import model_axis_dims
            self._chunk_dims = model_axis_dims(self.state.params,
                                               self._tp_chunks)
        else:
            self._chunk_dims = None
        # publish v0 weights, whatever the plane: the per-step pushes run
        # only while some engine pulls from the store (_push_async)
        self._publish_params(self.state.params, 0)

    # ------------------------------------------------------------------
    # rollout policy (runs inside the service tick via the tenant hooks)
    # ------------------------------------------------------------------
    def _next_job(self) -> Optional[RolloutJob]:
        """Trainer job source (service admission pulls from here): top up
        env groups unless the buffer is already ``max_buffered_batches``
        ahead of the trainer (backpressure: the service must not produce
        unboundedly). The backlog includes trajectories parked on
        unresolved reward futures, or slow serverless calls would defeat
        the bound. Returns one env-group job, or None when satisfied."""
        backlog = self.buffer.size() + len(self._pending_rewards)
        if (backlog >= self.cfg.batch_size
                * max(1, self.cfg.max_buffered_batches)):
            return None
        need_groups = int(np.ceil(
            self.cfg.batch_size / self.cfg.group_size * self.cfg.redundancy))
        alive = len({em.group_id for em in self.active
                     if em.state in (EMState.IDLE, EMState.GENERATING)})
        if alive >= need_groups:
            return None
        task = self.sampler.sample()
        gid = f"v{self.version}.g{alive}.{task}.{next(self._seed_counter)}"
        envs, seeds = [], []
        for _ in range(self.cfg.group_size):
            envs.append(make_env(task, seed=next(self._seed_counter)))
            seeds.append(next(self._seed_counter))
        return RolloutJob(
            kind="env", tag=task, envs=envs, seeds=seeds, group_id=gid,
            policy=RolloutPolicy(max_new_tokens=self.cfg.max_new_tokens,
                                 temperature=self.cfg.temperature),
            version=self.version,
            # the trainer consumes trajectories through the buffer, not
            # the per-job token stream — don't accumulate chunks nobody
            # reads (serving tenants opt in per job instead)
            stream=False)

    def _enforce_staleness(self):
        """RollArt: per-tick trajectory-level staleness control (tenant
        ``pre_tick`` hook, before admission)."""
        if self.cfg.mode == "areal":
            return   # AReaL bounds staleness at trajectory start only
        bound = self.version - self.cfg.alpha
        for em in list(self.active):
            if em.state == EMState.GENERATING and em.start_version < bound:
                em.abort()

    def _post_tick(self):
        """Tenant ``post_tick`` hook: redundant rollouts — once the
        buffer has a full batch, cancel the slowest in-flight rollouts
        beyond what the next iteration can use."""
        if (self.cfg.redundancy > 1.0
                and self.buffer.size() >= self.cfg.batch_size):
            self._cancel_surplus()

    def _cancel_surplus(self):
        """Abort only the surplus beyond ``batch_size * redundancy``
        in-flight trajectories (the headroom the next iteration launches
        with), slowest first — matching the simulator's per-iteration
        redundancy semantics. Aborting everything would also kill the
        groups the next batch needs and force cold restarts."""
        headroom = int(np.ceil(self.cfg.batch_size * self.cfg.redundancy))
        generating = [em for em in self.active
                      if em.state == EMState.GENERATING]
        surplus = len(generating) - headroom
        if surplus <= 0:
            return
        generating.sort(key=lambda em: em.turns)   # least progress first
        for em in generating[:surplus]:
            em.abort()

    def _observe_em(self, em: EnvManager):
        """Tenant ``observe`` hook (§9 online affinity profiling)."""
        prefill = sum(1 for m in em.loss_mask if m == 0)
        decode = len(em.tokens) - prefill
        self.profiler.observe(em.tag, prefill, decode, em.turns)

    def _on_em_complete(self, em: EnvManager):
        """Completion callback for managers resurrected OUTSIDE the
        service's job path (the FT snapshot restore re-wires restored
        managers here); same contract as the service's own hook."""
        with self._completed_lock:
            self._completed_this_round.append(em)

    # ------------------------------------------------------------------
    # service delegation shims (the FT plane and the test suite drive
    # the rollout plane through these; all dispatch is service-owned)
    # ------------------------------------------------------------------
    def _ensure_inflight(self):
        """Admit trainer jobs now (pulls :meth:`_next_job` dry)."""
        self.service.admit(only=TRAINER_TENANT)

    def _drain_completions(self) -> int:
        return self.service.drain_completions()

    def _drain_rewards(self, block: bool = False) -> int:
        return self.service.drain_rewards(block=block)

    def _drain_rollout(self):
        """Synchronous baselines: rollout and training strictly
        alternate, so — like the simulator's sync mode — leftover
        in-flight rollouts are CANCELLED after the batch, not completed
        into the next one."""
        self.service.drain_tenant(TRAINER_TENANT)

    @property
    def reward_retries(self) -> int:
        return self._tenant.stats["reward_retries"]

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def _start_rollout_worker(self):
        self.service.start()

    def _pause_rollout_worker(self):
        """Park the service thread; returns only once no tick is in
        flight (any tick that already passed the flag check finishes
        first)."""
        self.service.pause()

    def close(self):
        """Shut down the service thread and the weight-push thread.
        Idempotent and exception-safe: double-close is a no-op, and a
        close after a service-thread crash returns promptly instead of
        hanging on the join (regression: tests/test_rollout_service.py)."""
        if self._closed:
            return
        self._closed = True
        try:
            self.service.close()
        finally:
            try:
                self._await_push()
            finally:
                self._push_pool.shutdown(wait=True)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # ------------------------------------------------------------------
    # trainer side helpers
    # ------------------------------------------------------------------
    def _await_batch(self) -> List[Trajectory]:
        """Protocol step (1). Threaded modes block on the buffer (the
        service produces concurrently); synchronous modes tick the
        service cooperatively until a batch exists."""
        if self.threaded:
            deadline = time.monotonic() + self.cfg.batch_timeout_s
            while True:
                if self.service.error is not None:
                    raise RuntimeError("rollout worker died") \
                        from self.service.error
                try:
                    return self.buffer.get_batch(self.cfg.batch_size,
                                                 timeout=0.2)
                except TimeoutError:
                    if time.monotonic() > deadline:
                        raise RuntimeError(
                            "rollout starved: no batch collected")
        pumps = 0
        while True:
            batch = self.buffer.try_get_batch(self.cfg.batch_size)
            if batch is not None:
                return batch
            self.service.tick()
            pumps += 1
            if pumps > self.cfg.max_pump_steps:
                raise RuntimeError("rollout starved: no batch collected")

    def _publish_params(self, params, version: int) -> int:
        """Publish one weight version in the plane's format: per-shard
        chunks when any engine runs a TP group, dense otherwise. The FT
        restore path republishes through this too, so a restored plane
        keeps pulling the format its engines expect."""
        with spans.span("repro.weights.push"):
            if self._tp_chunks > 1:
                return push_params_sharded(self.store, params, version,
                                           self._tp_chunks,
                                           self._chunk_dims)
            return push_params(self.store, params, version)

    def _push_async(self):
        """Publish the new weights off-thread, if some engine pulls them
        from the store (one on the trainer's device takes the trainer's
        arrays at the barrier instead); the transfer overlaps the resumed
        rollout and is awaited at the next suspend barrier."""
        params, version = self.state.params, self.version
        if not self.proxy.weight_paths(params)[1]:
            return
        self._push_future = self._push_pool.submit(
            self._publish_params, params, version)

    def _await_push(self):
        if self._push_future is not None:
            self._push_future.result()
            self._push_future = None

    def _decode_tokens_total(self) -> int:
        return sum(h.engine.decode_tokens for h in self.proxy.handles)

    def _moe_decode_totals(self):
        """(held experts touched, assignments here) over the engines'
        decode blocks so far: lock-free reads, as ``decode_tokens``."""
        hs = self.proxy.handles
        return (sum(h.engine.moe_experts_touched for h in hs),
                sum(h.engine.moe_tokens_here for h in hs))

    def placement_report(self, **kw) -> List[Dict]:
        """Modeled prefill/decode latency + cost per engine pool (PerfModel
        pricing of the live placement; see LLMProxy.placement_report)."""
        return self.proxy.placement_report(**kw)

    # ------------------------------------------------------------------
    # the six-step protocol (the consumer thread)
    # ------------------------------------------------------------------
    def run_steps(self, num_steps: int) -> List[StepMetrics]:
        sync_like = self.cfg.mode in ("sync", "sync_plus")
        one_off = self.cfg.mode == "one_off"
        if self.threaded:
            self._start_rollout_worker()
        try:
            for _ in range(num_steps):
                with spans.phase("repro.runner.step") as whole:
                    sm = self._step(sync_like, one_off)
                sm.wall_s = whole.seconds
                self.history.append(sm)
        finally:
            if self.threaded:
                self._pause_rollout_worker()
            self._await_push()
        return self.history

    def _step(self, sync_like: bool, one_off: bool) -> StepMetrics:
        """One pass of the six-step protocol; the caller times the whole
        (``wall_s``) and records it."""
        step = len(self.history)
        # (1) get_batch. one_off trains on the PREVIOUS iteration's batch
        # (fetched at the end of the last step, so it was in hand before
        # this step began) while its successor rolls out.
        with spans.phase("repro.runner.fetch") as fetch:
            if one_off:
                if self._prev_batch is None:
                    self._prev_batch = self._await_batch()   # priming
                    self._prev_batch_fetched_step = -1
                batch_trajs = self._prev_batch
                fetched_step = self._prev_batch_fetched_step
            else:
                batch_trajs = self._await_batch()
                fetched_step = step
        self.last_batch = batch_trajs
        staleness = self.version - min(t.start_version
                                       for t in batch_trajs)
        # (2)-(5) the ONLY rollout/trainer barrier: suspend, update +
        # in-flight KV recompute, resume — atomic w.r.t. the service tick
        # so a weight swap never races a decode. The last step's push is
        # awaited first, where one is in flight (some engine pulls from
        # the store).
        push_wait_s = 0.0
        if self._push_future is not None:
            with spans.phase("repro.runner.push_wait") as push:
                self._await_push()
            push_wait_s = push.seconds
        with self.service.barrier():
            lock_wait_s = self.service.last_barrier_wait_s
            with spans.phase("repro.runner.sync") as sync:
                self.proxy.suspend()
                self._update_engines()
                self.proxy.resume()
                if self.barrier_hook is not None:
                    # rollout snapshot point: the service lock is held,
                    # so every engine slot / env manager / pending
                    # reward is quiescent and mutually consistent
                    self.barrier_hook(self, step)
        t_barrier = time.monotonic()
        # (6) train_step, overlapped with the resumed rollout
        with spans.phase("repro.runner.train") as train:
            with spans.span("repro.runner.pack"):
                batch = self._pack(batch_trajs)
            d0 = self._decode_tokens_total()
            self.state, metrics = self.train_step_fn(self.state, batch)
            loss = float(metrics["loss"])   # blocks until step done
        d1 = self._decode_tokens_total()
        self.version = int(self.state.version)
        self.buffer.set_version(self.version)
        if self.profiler is not None:
            with self.service.barrier():    # §9 online re-routing
                self.profiler.apply_to(self.proxy)
        self._push_async()
        if one_off:
            # the batch produced while we trained becomes the NEXT
            # iteration's training data
            self._prev_batch = self._await_batch()
            self._prev_batch_fetched_step = step
        if sync_like:
            self._drain_rollout()
        rewards = [t.reward for t in batch_trajs]
        ev_total = self.buffer.total_evicted
        ab_total = self.proxy.aborted
        rs_total = self.proxy.role_switches
        dd_total = self.buffer.total_deduped
        ab_tok_total = self._tenant.stats["aborted_tokens"]
        ev_tok_total = self.buffer.total_evicted_tokens
        host_total = self.service.tick_host_s
        moe_decode = self._moe_decode_totals()
        sm = StepMetrics(
            step=step, wall_s=0.0,
            loss=loss,
            reward_mean=float(np.mean(rewards)),
            evicted=ev_total - self._last_evicted,
            aborted=ab_total - self._last_aborted,
            trajs=len(batch_trajs),
            decode_during_train=d1 - d0,
            batch_fetched_step=fetched_step,
            batch_max_version=max(t.start_version for t in batch_trajs),
            role_switches=rs_total - self._last_role_switches,
            deduped=dd_total - self._last_deduped,
            fetch_s=fetch.seconds,
            barrier_s=t_barrier - fetch.t1,
            train_s=train.seconds,
            staleness=staleness,
            push_wait_s=push_wait_s,
            lock_wait_s=lock_wait_s,
            sync_s=sync.seconds,
            rollout_host_s=host_total - self._last_tick_host_s,
            aborted_tokens=ab_tok_total - self._last_aborted_tokens,
            evicted_tokens=ev_tok_total - self._last_evicted_tokens,
            moe_decode_experts_touched=(moe_decode[0]
                                        - self._last_moe_decode[0]),
            moe_decode_tokens_here=moe_decode[1] - self._last_moe_decode[1],
            moe_train_tokens_here=int(metrics.get("moe_tokens_here", 0)),
            moe_train_load_max=float(metrics.get("moe_load_max", 0.0)),
            moe_train_load_mean=float(metrics.get("moe_load_mean", 0.0)))
        self._last_evicted, self._last_aborted = ev_total, ab_total
        self._last_role_switches = rs_total
        self._last_deduped = dd_total
        self._last_aborted_tokens = ab_tok_total
        self._last_evicted_tokens = ev_tok_total
        self._last_tick_host_s = host_total
        self._last_moe_decode = moe_decode
        self.trained_log.append([t.traj_id for t in batch_trajs])
        return sm

    def _update_engines(self):
        """Protocol steps (3) and (5), under the service lock: every
        engine takes version ``self.version`` and recomputes its
        in-flight KV (a no-op for an engine already there). An engine
        on the trainer's own device takes ``self.state.params`` as they
        are, with no host round trip; the others pull the version the
        store holds, in the plane's format."""
        params = self.state.params
        local, remote = self.proxy.weight_paths(params)
        self.proxy.update_all(params, self.version, recompute_caches=True,
                              handles=local)
        if not remote:
            return
        if self._tp_chunks > 1:
            pulled = pull_param_chunks(self.store, params)
            if pulled is not None:
                chunks, v = pulled
                self.proxy.update_all_chunks(chunks, v,
                                             recompute_caches=True,
                                             handles=remote)
        else:
            pulled = pull_params(self.store, params)
            if pulled is not None:
                host, v = pulled
                self.proxy.update_all(host, v, recompute_caches=True,
                                      handles=remote)

    def _pack(self, trajs: List[Trajectory]) -> Dict:
        import jax.numpy as jnp
        # GRPO: group-normalize rewards within same-group trajectories,
        # falling back to batch normalization for stragglers
        by_group: Dict[str, List[Trajectory]] = {}
        for t in trajs:
            by_group.setdefault(t.group_id, []).append(t)
        rewards = np.asarray([t.reward for t in trajs], np.float32)
        adv = np.zeros_like(rewards)
        idx = {id(t): i for i, t in enumerate(trajs)}
        for group in by_group.values():
            r = np.asarray([t.reward for t in group], np.float32)
            mu, sd = r.mean(), r.std()
            base = (r - mu) / (sd + 1e-6) if len(group) > 1 else r - mu
            for t, a in zip(group, base):
                adv[idx[id(t)]] = a
        batch = pack_batch(trajs, self.seq_len)
        batch["advantages"] = adv
        return {k: jnp.asarray(v) for k, v in batch.items()}
