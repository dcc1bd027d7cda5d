"""One run of a cell: the live GRPO loop of ``repro`` under a traffic mix,
set up, measured over a window, and checked against the reference.

The plane is the one ``repro.launch.train.build_runner`` builds (one
colocated engine behind an ``LLMProxy``, driven by ``LiveRLRunner`` in the
mix's mode), made from the same public constructors with the same
arguments, except that the runner and the engine take the run's seed:
``build_runner`` fixes both at 0. Weights come from the seed on the
device, in one jitted call.

Set-up runs, in order: the model and train state, the plane, one request
per prefill bucket a prompt or a recompute of the mix can fall in (so
every engine program is compiled before the runner starts), then
``WARMUP_STEPS`` whole train steps, the first of which compiles the GRPO
step. The window starts at the end of the last warm-up step and ends at
the end of the first step that finishes ``seconds`` or more later; the
runner stops at the next barrier. Every rate divides by the window's own
length.

A mix (``traffic/<name>.json``) holds what the launcher takes as
arguments for the job (tasks and their weights, mode, alpha, batch,
group, decode steps per dispatch, reward), how the benchmark draws
tasks (``task_block``) and, where its rows make rounding read
differently, the limits of its own (``limits``); what ``build_runner``
fixes is fixed here too.
"""
from __future__ import annotations

import functools
import gc
import random
import tempfile
import threading
import time
import zlib
from collections import Counter
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Dict, List, Optional, Tuple

import numpy as np

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = COMPILE_EVENTS[-1]
# a traced run measures a window of at most this long: a trace of the
# live loop grows by some 6 MB a second, and reading it back must stay
# well inside a run's time limit
TRACE_SECONDS = 10.0

# what ``build_runner`` fixes: one engine of 8 slots and 640 positions,
# trained rows of 640 tokens, and the launcher's ``--lr`` default
MAX_SLOTS, MAX_LEN, SEQ_LEN = 8, 640, 640
LR = 1e-3
# whole train steps in set-up: the first compiles the GRPO step, the
# window opens at the end of the last
WARMUP_STEPS = 4
# served turns the check re-scores, the longest always among them
CHECK_TURNS = 16
# width of the seeded score added to each rule-based reward (``Reward``)
REWARD_JITTER = 0.1


class StopWindow(Exception):
    """Raised from the barrier after the window's last step."""


class CompileLog:
    """Programs traced, lowered and compiled (or read back from the
    persistent cache) by this process, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            with self._lock:
                self.seconds += duration
                if event == BACKEND_COMPILE:
                    self.programs += 1

    def snapshot(self) -> Tuple[int, float]:
        with self._lock:
            return self.programs, self.seconds


def model_config(cfg: Dict):
    """The system's ``ModelConfig`` for a Hugging Face style
    ``config.json`` dict. The keys every decoder config has map here; what
    the system needs beyond them (family, block pattern, qk-norm) is in
    the configuration file's ``system_config``."""
    from repro.configs.base import ModelConfig

    def tuples(v):
        return tuple(map(tuples, v)) if isinstance(v, list) else v
    return ModelConfig(
        name=cfg["name"], source=cfg["source"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), dtype=cfg["torch_dtype"],
        param_dtype=cfg["param_dtype"], norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        **{k: tuples(v) for k, v in cfg["system_config"].items()})


def runner_config(traffic: Dict, seed: int):
    from repro.core import RunnerConfig
    return RunnerConfig(
        batch_size=traffic["batch_size"], group_size=traffic["group_size"],
        alpha=traffic["alpha"], mode=traffic["mode"],
        tasks=tuple(traffic["tasks"]),
        task_weights=tuple(float(w) for w in traffic["task_weights"]),
        pd_disagg=False, pools=None, affinity=False,
        steps_per_dispatch=traffic["steps_per_dispatch"], seed=seed)


def build_runner(model, state, step_fn, traffic: Dict, seed: int,
                 reward_fn):
    """The plane ``build_runner`` makes for this mix, at ``seed``."""
    import jax
    from repro.core import (EngineHandle, LiveRLRunner, LLMProxy,
                            ServerlessPlatform)
    from repro.core.hardware import device_pool
    from repro.rl.engine import InferenceEngine
    eng = InferenceEngine(
        model, state.params, max_slots=MAX_SLOTS, max_len=MAX_LEN,
        steps_per_dispatch=traffic["steps_per_dispatch"], mesh=None,
        seed=seed & 0xFFFFFFFF)
    proxy = LLMProxy([EngineHandle(eng, device_pool(jax.devices()[0]))])
    return LiveRLRunner(runner_config(traffic, seed), proxy, state, step_fn,
                        ServerlessPlatform(), reward_fn, seq_len=SEQ_LEN)


class BalancedTasks:
    """The mix's task draws, in place of the runner's independent weighted
    draws: blocks of ``block`` draws in which each task comes up in
    proportion to its weight, each block in an order drawn from the seed.
    Every seed then sends the same work in another order, so runs of
    different seeds differ by the system's noise, not by how many long
    episodes the dice gave them."""

    def __init__(self, tasks, weights, block: int, seed: int):
        total = sum(weights)
        counts = [round(w / total * block) for w in weights]
        if sum(counts) != block or any(
                abs(c / block - w / total) > 1e-9
                for c, w in zip(counts, weights)):
            raise ValueError(f"weights {weights} do not split a block of "
                             f"{block} draws exactly")
        self._block = [t for t, c in zip(tasks, counts) for _ in range(c)]
        self._rng = random.Random(seed)
        self._queue: List[str] = []

    def sample(self) -> str:
        if not self._queue:
            self._queue = list(self._block)
            self._rng.shuffle(self._queue)
        return self._queue.pop()


class Reward:
    """The mix's rule-based reward plus a score in ``[0, jitter)`` drawn
    from the seed and the trajectory's tokens, standing in for a reward
    model's continuous score: with random weights every rule-based reward
    of a group ties, GRPO's advantages vanish and the train step's
    gradient is exactly zero."""

    def __init__(self, base, jitter: float, seed: int):
        self.base, self.jitter = base, jitter
        self.seed = seed & 0xFFFFFFFF

    def __call__(self, payload: Dict) -> float:
        import jax
        with jax.profiler.TraceAnnotation("bench.reward"):
            toks = np.asarray(payload["tokens"], np.int32).tobytes()
            u = zlib.crc32(toks, self.seed) / 2.0 ** 32
            return float(self.base(payload)) + self.jitter * u


@dataclass
class Recorder:
    """The train step handed to the runner, wrapped: it times each step,
    keeps what the check compares from the first three, and opens and
    closes the window at step boundaries."""
    step_fn: object
    warmup: int
    seconds: float
    b1: float
    compile_log: CompileLog
    trace_dir: Optional[str] = None
    runner: object = None
    p0: object = None
    calls: int = 0
    losses: List[float] = field(default_factory=list)
    batches: List[Dict] = field(default_factory=list)
    grad_norms: Dict[str, float] = field(default_factory=dict)
    change_norms: Dict[str, float] = field(default_factory=dict)
    lengths: List[List[int]] = field(default_factory=list)
    trajs: List[list] = field(default_factory=list)
    train_end: List[float] = field(default_factory=list)
    versions: Dict[int, List[int]] = field(default_factory=dict)
    weight_gaps: list = field(default_factory=list)
    lifecycles: list = field(default_factory=list)
    t_start: Optional[float] = None
    t_end: Optional[float] = None
    at_start: Dict = field(default_factory=dict)
    at_end: Dict = field(default_factory=dict)
    stop: bool = False
    _between = None
    _window = None

    def __call__(self, state, batch):
        import jax
        i = self.calls
        self.calls += 1
        if self._between is not None:
            self._between.__exit__(None, None, None)
        with jax.profiler.TraceAnnotation("bench.train_step"):
            new_state, metrics = self.step_fn(state, batch)
            loss = float(metrics["loss"])
        t = time.monotonic()
        self.train_end.append(t)
        self.trajs.append(list(self.runner.last_batch))
        self.lengths.append([min(len(tr.tokens), batch["tokens"].shape[1])
                             for tr in self.runner.last_batch])
        self.lifecycles += self.runner.proxy.drain_completed_lifecycles()
        if i < 3:
            self.losses.append(loss)
            self.batches.append({k: np.asarray(v) for k, v in batch.items()})
        if i == 0:
            # AdamW's first moment after one step is (1 - b1) x gradient
            self.grad_norms = {k: v / (1 - self.b1) for k, v in
                               _norms(programs().norms(new_state.opt.mu))
                               .items()}
        if i == 2:
            self.change_norms = _norms(programs().change_norms(
                new_state.params, self.p0))
            self.p0 = None
        if self.trace_dir is not None and i == self.warmup - 2:
            # the runtime's host events, without a Python frame per call:
            # the Python tracer slows the host-bound rollout it measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        if i == self.warmup - 1:
            self.t_start = t
            self.at_start = self._counters()
            if self.trace_dir is not None:
                self._window = jax.profiler.TraceAnnotation("bench.window")
                self._window.__enter__()
        elif (i >= self.warmup and not self.stop
              and t - self.t_start >= self.seconds):
            self.t_end = t
            self.stop = True
            self.at_end = self._counters()
            if self._window is not None:
                self._window.__exit__(None, None, None)
        self._between = jax.profiler.TraceAnnotation("bench.fetch_barrier")
        self._between.__enter__()
        return new_state, metrics

    def _counters(self) -> Dict:
        r = self.runner
        engines = [h.engine.stats() for h in r.proxy.handles]
        out = {k: sum(e[k] for e in engines)
               for k in ("prefill_tokens", "decode_tokens",
                         "decode_dispatches", "recomputes")}
        out["tenant"] = r.service.stats()["trainer"]
        out["compile"] = self.compile_log.snapshot()
        return out

    def on_barrier(self, runner, step: int):
        """The runner's barrier hook: the engines have just taken the
        step's weights. Each engine's weights are set against the
        trainer's on the device, without waiting for the answer: the
        largest gap of any element, read once the window has closed."""
        self.versions[step] = [h.engine.stats()["weight_version"]
                               for h in runner.proxy.handles]
        self.weight_gaps += [programs().worst_gap(h.engine.params,
                                                  runner.state.params)
                             for h in runner.proxy.handles]
        if self.stop:
            raise StopWindow()

    def close_spans(self):
        if self._between is not None:
            self._between.__exit__(None, None, None)
            self._between = None


@functools.cache
def programs():
    """The jitted programs the recorder runs on the device, built once a
    process."""
    import jax
    import jax.numpy as jnp

    def norm(x):
        return jnp.linalg.norm(x.ravel().astype(jnp.float32))

    def worst_gap(served, trained):
        """Largest ``|served - trained|`` of any element, each trainer
        leaf taken in its engine leaf's type first: a serving copy in a
        narrower type that holds the trainer's weights reads 0."""
        return jnp.max(jnp.stack(jax.tree.leaves(jax.tree.map(
            lambda a, b: jnp.max(jnp.abs(
                a.astype(jnp.float32)
                - b.astype(a.dtype).astype(jnp.float32))),
            served, trained))))

    return SimpleNamespace(
        norms=jax.jit(lambda t: jax.tree.map(norm, t)),
        change_norms=jax.jit(lambda p, q: jax.tree.map(
            lambda a, b: norm(a - b), p, q)),
        worst_gap=jax.jit(worst_gap))


def _norms(tree) -> Dict[str, float]:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): float(v) for k, v in flat}


def prefill_buckets(engine, longest: int) -> List[int]:
    """Every prefill length the engine pads a prompt or a recompute of at
    most ``longest`` tokens to."""
    return sorted({engine._bucket_len(n) for n in range(1, longest + 1)})


def warm_engine(proxy, buckets, max_new: int):
    """One request per prefill bucket length through the proxy, so the
    engine compiles each prefill shape and its decode block before the
    runner starts."""
    from repro.rl.engine import GenRequest
    done = []
    for n in buckets:
        proxy.submit(GenRequest(request_id=f"warmup.{n}", prompt=[1] * n,
                                max_new_tokens=max_new, temperature=1.0,
                                tag="warmup"),
                     callback=done.append)
    while len(done) < len(buckets):
        proxy.pump()
    proxy.drain_completed_lifecycles()


def action_turns(traj, limit: int):
    """``(request index, context + served tokens, start, engine logprobs)``
    for each policy turn of a trajectory: each run of ``loss_mask == 1``
    behind the last ``limit`` tokens before it (the prompt the request
    carried). Request indices are known only where every turn produced
    tokens; otherwise they are None."""
    m, runs, i = traj.loss_mask, [], 0
    while i < len(m):
        if m[i] != 1:
            i += 1
            continue
        j = i
        while j < len(m) and m[j] == 1:
            j += 1
        runs.append((i, j))
        i = j
    exact = len(runs) == traj.turns
    for n, (i, j) in enumerate(runs):
        lo = max(0, i - limit)
        yield (n if exact else None, traj.tokens[lo:j], i - lo,
               traj.logprobs[i:j])


def sample_turns(turns: List, k: int, seed: int) -> List:
    """``k`` turns drawn from the seed, always with the longest."""
    if len(turns) <= k:
        return list(turns)
    order = sorted(range(len(turns)), key=lambda t: -len(turns[t][0]))
    rest = order[1:]
    random.Random(seed).shuffle(rest)
    return [turns[t] for t in [order[0]] + rest[:k - 1]]


@dataclass
class Run:
    """What one run measured; the per-layer readers take their numbers
    from here."""
    cell: object
    setup_s: float
    window_s: float
    steps: list
    lengths: List[List[int]]
    lifecycles: list
    counters: Dict
    compile: Dict
    versions: Dict
    shapes: object
    peaks: Dict
    chips: int
    plane: Dict
    trace: Optional[Dict] = None
    readings: Dict = field(default_factory=dict)
    compared: Dict = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    aborted: int = 0
    latencies: List[float] = field(default_factory=list)
    memory_peak_bytes: int = 0
    program: object = None
    batches: List[Dict] = field(default_factory=list)
    trained_tasks: Dict[str, int] = field(default_factory=dict)


def window_requests(lifecycles, t0: float, t1: float):
    return [lc for lc in lifecycles
            if not lc.request_id.startswith("warmup.")
            and lc.t_admit is not None and t0 <= lc.t_admit <= t1]


def trajectory_latencies(lifecycles, active_ids, t0: float, t1: float):
    """First request's submit to last request's finish, for every
    trajectory whose last request finished (not aborted) in the window
    and which is no longer in flight."""
    by_traj: Dict[str, list] = {}
    for lc in lifecycles:
        if lc.request_id.startswith("warmup.") or lc.t_finish is None:
            continue
        traj, _, n = lc.request_id.rpartition(".r")
        by_traj.setdefault(traj, []).append((int(n), lc))
    out = []
    for traj, reqs in by_traj.items():
        reqs.sort(key=lambda x: x[0])
        first, last = reqs[0][1], reqs[-1][1]
        if (traj in active_ids or reqs[0][0] != 0
                or last.finish_reason == "aborted"
                or not t0 <= last.t_finish <= t1):
            continue
        out.append(last.t_finish - first.t_submit)
    return out


def started_in(lifecycles, t0: float, t1: float) -> int:
    return sum(1 for lc in lifecycles
               if lc.request_id.endswith(".r0")
               and t0 <= lc.t_submit <= t1)


def run(cell, seed: int, seconds: float, trace: bool, t_process: float,
        compile_log: CompileLog) -> Run:
    """Set up, measure and check one run of ``cell``."""
    import jax
    from repro.core.envmanager import RolloutPolicy
    from repro.models import Model
    from repro.rewards.rule_based import REWARD_FNS
    from repro.rl import trainer as TR
    from benchmarks.chip import check, work

    cfg, traffic = cell.config, cell.traffic
    dev = jax.devices()[0]
    peaks = work.peaks(dev.device_kind) if dev.platform == "tpu" else {}
    model = Model(model_config(cfg), remat=False)
    opt = TR.default_optimizer(LR)
    state = jax.jit(lambda k: TR.init_train_state(model, k, opt))(
        jax.random.PRNGKey(seed & 0xFFFFFFFF))
    step_fn = jax.jit(TR.make_grpo_train_step(model, opt))
    warmup = WARMUP_STEPS
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    rec = Recorder(step_fn=step_fn, warmup=warmup,
                   seconds=min(seconds, TRACE_SECONDS) if trace else seconds,
                   b1=opt.b1, compile_log=compile_log,
                   trace_dir=trace_dir, p0=state.params)
    reward = Reward(REWARD_FNS[traffic["reward"]], REWARD_JITTER, seed)
    runner = build_runner(model, state, rec, traffic, seed, reward)
    del state
    rec.runner = runner
    runner.barrier_hook = rec.on_barrier
    runner.sampler = BalancedTasks(traffic["tasks"], traffic["task_weights"],
                                   traffic["task_block"], seed)
    engine = runner.proxy.handles[0].engine
    max_new = runner.cfg.max_new_tokens
    prompt_limit = RolloutPolicy().max_prompt_tokens
    # a prompt holds at most ``prompt_limit`` tokens; a recompute at the
    # barrier re-prefills a prompt and the tokens served after it
    warm_engine(runner.proxy, prefill_buckets(engine, prompt_limit + max_new),
                max_new)
    plane = {"slots": engine.max_slots,
             "steps_per_dispatch": engine.steps_per_dispatch}
    del engine
    active_ids = set()
    with runner:
        try:
            runner.run_steps(10 ** 9)
        except StopWindow:
            pass
        finally:
            rec.close_spans()
        if trace_dir is not None:
            jax.profiler.stop_trace()
        rec.lifecycles += runner.proxy.drain_completed_lifecycles()
        history = list(runner.history)
        active_ids = {em.em_id for em in runner.active}
    runner.proxy.release_bindings()
    peak = max(d.memory_stats()["peak_bytes_in_use"]
               for d in jax.local_devices()) if dev.platform == "tpu" else 0
    weight_gap = (max(float(g) for g in rec.weight_gaps)
                  if rec.weight_gaps else None)
    rec.weight_gaps = []
    del runner, model, opt, step_fn
    rec.runner = rec.step_fn = None
    gc.collect()

    t0, t1 = rec.t_start, rec.t_end
    n_steps = len(rec.train_end) - warmup
    lcs = rec.lifecycles
    tenant0, tenant1 = rec.at_start["tenant"], rec.at_end["tenant"]
    r = Run(
        cell=cell, setup_s=t0 - t_process, window_s=t1 - t0,
        steps=history[warmup:warmup + n_steps],
        lengths=rec.lengths[warmup:warmup + n_steps],
        lifecycles=window_requests(lcs, t0, t1),
        counters={k: rec.at_end[k] - rec.at_start[k]
                  for k in ("prefill_tokens", "decode_tokens",
                            "decode_dispatches", "recomputes")},
        compile={"setup_programs": rec.at_start["compile"][0],
                 "setup_seconds": rec.at_start["compile"][1],
                 "window_programs": (rec.at_end["compile"][0]
                                     - rec.at_start["compile"][0])},
        versions=rec.versions, shapes=work.Shapes.from_config(cfg),
        peaks=peaks, chips=cell.chips, plane=plane,
        attempted=started_in(lcs, t0, t1),
        failed=tenant1.get("failed", 0) - tenant0.get("failed", 0),
        aborted=tenant1.get("aborted", 0) - tenant0.get("aborted", 0),
        latencies=trajectory_latencies(lcs, active_ids, t0, t1),
        memory_peak_bytes=int(peak),
        trained_tasks=dict(Counter(
            t.task for step in rec.trajs[warmup:warmup + n_steps]
            for t in step)))
    if trace_dir is not None:
        from benchmarks.chip import xplane
        import glob
        import shutil
        files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
        r.trace = xplane.reduce(xplane.load(files[0])) if files else None
        shutil.rmtree(trace_dir, ignore_errors=True)

    # what the check compares: the first three steps, and the turns the
    # engine served on the seed's weights (version 0), prefill and decode
    v0 = [(s, a, lp) for step in rec.trajs for t in step
          if t.start_version == 0 and t.version == 0
          for _, s, a, lp in action_turns(t, prompt_limit)]
    program = check.ProgramSide(
        losses=rec.losses, grad_norms=rec.grad_norms,
        change_norms=rec.change_norms,
        v0_turns=sample_turns(_dedup(v0), CHECK_TURNS, seed),
        versions=rec.versions, weight_gap=weight_gap)
    r.program, r.batches = program, rec.batches
    r.readings = check.compare(cfg, seed, rec.batches, program)
    r.compared = check.checks(cfg, r.readings, traffic.get("limits"))
    return r


def _dedup(turns):
    """Keep each turn once (a trajectory is listed once per step that
    trained it)."""
    seen, out = set(), []
    for s, a, lp in turns:
        key = (tuple(s), a)
        if key not in seen:
            seen.add(key)
            out.append((s, a, lp))
    return out
