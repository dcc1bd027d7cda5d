"""Live demonstration of genuine train/rollout overlap (paper principle 2,
trajectory-level asynchrony): the rollout side — proxy pump, EnvManager
completions, async serverless reward scoring — runs on a persistent
background worker thread that keeps filling the SampleBuffer while the
trainer thread executes the six-step weight-sync protocol. The per-step
``ovl`` column counts decode tokens the engines generated WHILE train_step
ran; run with ``--mode sync`` to see it collapse to zero.

    PYTHONPATH=src python examples/train_async_overlap.py --steps 6
    PYTHONPATH=src python examples/train_async_overlap.py --mode sync
    PYTHONPATH=src python examples/train_async_overlap.py --mode one_off
"""
import argparse
import time

import jax

from repro.configs import get_config
from repro.core import (EngineHandle, LiveRLRunner, LLMProxy, RunnerConfig,
                        ServerlessPlatform)
from repro.models import Model
from repro.rewards.rule_based import format_bonus_reward
from repro.rl.engine import InferenceEngine
from repro.rl.trainer import (default_optimizer, init_train_state,
                              make_grpo_train_step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--group", type=int, default=2)
    ap.add_argument("--alpha", type=int, default=1)
    ap.add_argument("--mode", default="rollart",
                    choices=["rollart", "areal", "one_off", "sync",
                             "sync_plus"])
    ap.add_argument("--tasks", default="game")
    ap.add_argument("--max-new-tokens", type=int, default=12)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    model = Model(cfg, remat=False)
    opt = default_optimizer(1e-3)
    state = init_train_state(model, jax.random.PRNGKey(0), opt)
    eng = InferenceEngine(model, state.params, max_slots=8, max_len=256,
                          seed=3)
    proxy = LLMProxy([EngineHandle(eng, "H20")])

    t0 = time.time()
    with LiveRLRunner(
            RunnerConfig(batch_size=args.batch, group_size=args.group,
                         alpha=args.alpha, mode=args.mode,
                         tasks=tuple(args.tasks.split(",")),
                         max_new_tokens=args.max_new_tokens),
            proxy, state, jax.jit(make_grpo_train_step(model, opt)),
            ServerlessPlatform(), format_bonus_reward,
            seq_len=256) as runner:
        print(f"mode={args.mode} "
              f"({'threaded rollout worker' if runner.threaded else 'cooperative'})")
        for h in runner.run_steps(args.steps):
            print(f"step {h.step:2d}  loss {h.loss:+.4f}  "
                  f"reward {h.reward_mean:+.3f}  wall {h.wall_s:5.2f}s  "
                  f"ovl {h.decode_during_train:4d} decode toks  "
                  f"batch_from_step {h.batch_fetched_step:2d}  "
                  f"evicted {h.evicted}  aborted {h.aborted}")
        total_ovl = sum(h.decode_during_train for h in runner.history)
        print(f"\ndone in {time.time() - t0:.0f}s; decode tokens generated "
              f"during train_step: {total_ovl} "
              f"({'overlap is live' if total_ovl else 'no overlap — synchronous baseline'}); "
              f"reward calls: {runner.serverless.stats.invocations}; "
              f"{_weight_syncs(runner)}")


def _weight_syncs(runner) -> str:
    """Where the engines took their weight versions: the trainer's own
    device arrays, or the store (which publishes v0 and then only while
    some engine pulls from it)."""
    st = [h.engine.stats() for h in runner.proxy.handles]
    return (f"weight versions taken on the trainer's device "
            f"{sum(s['local_syncs'] for s in st)}, from the store "
            f"{sum(s['store_syncs'] for s in st)} "
            f"({runner.store.log.pushes} published)")


if __name__ == "__main__":
    main()
