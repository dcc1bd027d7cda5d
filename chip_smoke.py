"""Chip smoke: the live agentic-RL loop on a TPU at Qwen3-8B widths.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the disaggregated plane

One chip: ``LiveRLRunner.run_steps(3)`` in ``rollart`` mode over one
colocated engine (K=8 decode blocks), the default task mix and
``ServerlessPlatform`` rewards. Four chips: only the plane that
``repro.launch.train --pd-disagg --devices-per-engine 2`` builds, a
prefill engine on chips 0-1 and a decode engine on chips 2-3 with the KV
handoff between them and per-shard weight sync, for ``run_steps(2)``.
Both build the plane with the launcher's own ``build_parser`` /
``build_runner``, at ``CHIP_SMOKE`` (``configs/qwen3_8b.py``: every
Qwen3-8B width, one layer, an eighth of the vocabulary, random weights
from seed 0).

Each check prints one line and fails the script when it is not met; the
last line is ``{"ok": true, "device": {...}}``. JAX must find a TPU: the
script refuses any other platform. Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
# libtpu otherwise writes its logs under /tmp, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

# Engine behaviour logprobs against a teacher-forced forward on the same
# weights. Both sides compute in bf16 and round their logits to bf16
# before the f32 softmax, so one logit can differ by one bf16 step
# (2**-8 relative, ~0.016 at |logit| ~ 3) between the engine's prefill +
# cached decode and the full-sequence forward, which run different op
# sequences. A real fault (wrong position, stale KV, wrong weights)
# moves a logprob by O(1) nats over this random 18,992-way vocabulary.
LOGPROB_MAX_ABS_TOL = 0.25
LOGPROB_MEAN_ABS_TOL = 0.02

# policy turns of step 0 compared, spread evenly over its trajectories
AGREEMENT_TURNS = 64

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


class CompileLog:
    """XLA compiles of this process, read from JAX's monitoring events
    (a persistent-cache hit is counted at its retrieval time)."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self.seconds += duration
                self.programs += 1

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1


def turn_windows(traj, limit: int):
    """One ``(context + action tokens, action start, engine logprobs)``
    per policy turn of ``traj``: each run of ``loss_mask == 1`` tokens,
    behind the last ``limit`` tokens before it (the prompt the
    EnvManager sent for that turn)."""
    m, i = traj.loss_mask, 0
    while i < len(m):
        if m[i] != 1:
            i += 1
            continue
        j = i
        while j < len(m) and m[j] == 1:
            j += 1
        lo = max(0, i - limit)
        yield traj.tokens[lo:j], i - lo, traj.logprobs[i:j]
        i = j


def run(cfg, *, four_chips: bool) -> None:
    """Drive the live RL loop at ``cfg`` and check what comes out."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.envmanager import RolloutPolicy
    from repro.launch import train as T
    from repro.models import Model
    from repro.rl.trainer import (default_optimizer, init_train_state,
                                  make_grpo_train_step, make_logprob_fn)

    steps = 2 if four_chips else 3
    argv = ["--steps", str(steps)]
    if four_chips:
        argv += ["--pd-disagg", "--devices-per-engine", "2"]
    args = T.build_parser().parse_args(argv)
    model = Model(cfg, remat=False)
    opt = default_optimizer(args.lr)
    state = init_train_state(model, jax.random.PRNGKey(0), opt)
    params0 = state.params          # weight version 0, kept for the check
    full_bytes = sum(x.nbytes for x in jax.tree.leaves(params0))
    print(f"config: {cfg.name} layers={cfg.num_layers} d={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads}x{cfg.head_dim} "
          f"ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype} "
          f"params={sum(x.size for x in jax.tree.leaves(params0))} "
          f"({full_bytes} B {cfg.param_dtype})", flush=True)
    step_fn = jax.jit(make_grpo_train_step(model, opt))
    runner = T.build_runner(args, model, state, step_fn)

    # the barrier hook runs with the rollout plane quiescent, right after
    # the engines took the step's weights
    barriers = []

    def on_barrier(r, step):
        barriers.append({
            "versions": [h.engine.stats()["weight_version"]
                         for h in r.proxy.handles],
            "decode_tokens": sum(h.engine.stats()["decode_tokens"]
                                 for h in r.proxy.handles),
            "batch": list(r.last_batch)})
    runner.barrier_hook = on_barrier

    engines = [h.engine for h in runner.proxy.handles]
    for h in runner.proxy.handles:
        devs = (h.engine.mesh.devices.ravel().tolist()
                if h.engine.mesh is not None else [jax.devices()[0]])
        print(f"engine: {h.name or 'local'} role={h.role} pool={h.pool} "
              f"devices={[d.id for d in devs]}", flush=True)
    lead = jax.tree.leaves(state.params)[0]
    print(f"trainer: state on devices "
          f"{sorted(d.id for d in lead.devices())}", flush=True)
    del state, lead                 # the runner owns the train state now

    with runner:
        hist = runner.run_steps(steps)
        final_version = runner.version
        decode_total = sum(e.stats()["decode_tokens"] for e in engines)
    runner.proxy.release_bindings()

    check(len(hist) == steps and len(barriers) == steps,
          f"ran {len(hist)} steps / {len(barriers)} barriers, "
          f"wanted {steps}")
    for m, b in zip(hist, barriers):
        toks = sum(len(t.tokens) for t in b["batch"])
        print(f"step {m.step}: loss={m.loss!r} reward_mean={m.reward_mean!r}"
              f" trajs={m.trajs} traj_tokens={toks} "
              f"decode_tokens={b['decode_tokens']} "
              f"ovl_decode_tokens={m.decode_during_train} "
              f"engine_version={b['versions']} staleness={m.staleness} "
              f"wall_s={m.wall_s!r} train_s={m.train_s!r}", flush=True)
        check(math.isfinite(m.loss), f"step {m.step}: loss {m.loss}")
        # the barrier of step i swaps in the weights trained by step i-1
        check(all(v == m.step for v in b["versions"]),
              f"step {m.step}: engines at versions {b['versions']}")
    check(final_version == steps,
          f"trainer at version {final_version} after {steps} steps")
    check(decode_total > 0, "engines decoded no tokens")
    print(f"decode: {decode_total} tokens in {steps} steps", flush=True)

    # agreement: step 0's trajectories, all produced under version 0,
    # against a teacher-forced forward on the version-0 weights, one
    # window per policy turn holding exactly the context that turn's
    # request carried
    batch0 = barriers[0]["batch"]
    check(all(t.start_version == 0 and t.version == 0 for t in batch0),
          "a step-0 trajectory carries a version other than 0")
    policy = RolloutPolicy()
    wins = [w for t in batch0
            for w in turn_windows(t, policy.max_prompt_tokens)]
    check(bool(wins), "step 0 holds no policy turn")
    wins = wins[::max(1, len(wins) // AGREEMENT_TURNS)][:AGREEMENT_TURNS]
    seq = policy.max_prompt_tokens + runner.cfg.max_new_tokens
    seq = -(-seq // 128) * 128
    lp_fn = jax.jit(make_logprob_fn(model))
    diffs = []
    for i in range(0, len(wins), 8):
        group = wins[i:i + 8]
        toks = np.zeros((8, seq), np.int32)
        for r, (ctx, _, _) in enumerate(group):
            toks[r, :len(ctx)] = ctx
        lp = np.asarray(lp_fn(params0, jnp.asarray(toks)))
        for r, (ctx, start, engine_lp) in enumerate(group):
            tf = lp[r, start - 1:start - 1 + len(engine_lp)]
            diffs.append(np.abs(tf - np.asarray(engine_lp, np.float32)))
    diff = np.concatenate(diffs)
    print(f"agreement: {len(wins)} turns, {diff.size} sampled tokens: "
          f"max |engine - teacher-forced| logprob = {float(diff.max())!r} "
          f"(tol {LOGPROB_MAX_ABS_TOL}), mean = {float(diff.mean())!r} "
          f"(tol {LOGPROB_MEAN_ABS_TOL}); bf16 logits on both sides",
          flush=True)
    check(float(diff.max()) <= LOGPROB_MAX_ABS_TOL
          and float(diff.mean()) <= LOGPROB_MEAN_ABS_TOL,
          "engine logprobs disagree with the teacher-forced forward")

    if four_chips:
        check(runner.proxy.max_group_size() == 2, "engines are not TP2")
        for h in runner.proxy.handles:
            per_dev = h.engine.param_device_bytes()
            sync = h.engine.stats()["sync_bytes"]
            print(f"params: {h.name} {per_dev} of {full_bytes} B full; "
                  f"chunked sync consumed {sync} B", flush=True)
            check("host" not in per_dev and len(per_dev) == 2,
                  f"{h.name}: params not on its two chips: {per_dev}")
            check(max(per_dev.values()) < full_bytes,
                  f"{h.name}: a device holds the full params")
            check(sync > 0, f"{h.name}: no per-shard weight sync ran")

    for d in jax.local_devices():
        ms = d.memory_stats()
        check(ms is not None, f"device {d.id} reports no memory stats")
        print(f"memory: device {d.id} peak_bytes_in_use="
              f"{ms['peak_bytes_in_use']} bytes_limit="
              f"{ms.get('bytes_limit')}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the disaggregated prefill TP2 -> "
                         "decode TP2 plane, on four chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: JAX found platform {d0.platform!r} "
              f"({d0.device_kind}), not a TPU; refusing to run",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    check(len(devices) >= need,
          f"needs {need} chips, JAX found {len(devices)}")

    from repro.configs.qwen3_8b import CHIP_SMOKE
    from repro.launch.compile_cache import enable_compile_cache
    # the four-chip plane runs TP2 engine groups, which keep the cache off
    cache_dir = enable_compile_cache(2 if args.four_chips else 1)
    log = CompileLog()
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}", flush=True)
    t0 = time.monotonic()
    run(CHIP_SMOKE, four_chips=args.four_chips)
    cache = (f"persistent cache at {cache_dir}: {log.hits} hits, "
             f"{log.misses} misses" if cache_dir else "persistent cache off")
    print(f"compile: {log.programs} programs, {log.seconds!r} s ({cache}); "
          f"run {time.monotonic() - t0!r} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
