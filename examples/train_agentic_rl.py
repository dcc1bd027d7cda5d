"""End-to-end agentic RL driver (deliverable (b)): the full RollArt pipeline
— trajectory-level rollout against real environments through the LLMProxy,
serverless reward scoring, the bounded-staleness SampleBuffer, GRPO updates,
and the six-step weight-sync protocol with KV-cache recomputation — on a
small model, live on CPU.

    PYTHONPATH=src python examples/train_agentic_rl.py --steps 20
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
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--group", type=int, default=4)
    ap.add_argument("--alpha", type=int, default=1)
    ap.add_argument("--tasks", default="math,game")
    ap.add_argument("--mode", default="rollart",
                    choices=["rollart", "areal", "one_off", "sync",
                             "sync_plus"])
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--max-new-tokens", type=int, default=24)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    model = Model(cfg, remat=False)
    opt = default_optimizer(args.lr)
    state = init_train_state(model, jax.random.PRNGKey(0), opt)
    step_fn = jax.jit(make_grpo_train_step(model, opt))

    # two engines on different "hardware classes"; prefill-heavy tasks are
    # routed to the compute pool, decode-heavy to the bandwidth pool (R1)
    e1 = InferenceEngine(model, state.params, max_slots=8, max_len=640,
                         seed=1)
    e2 = InferenceEngine(model, state.params, max_slots=8, max_len=640,
                         seed=2)
    proxy = LLMProxy(
        [EngineHandle(e1, "H800", "gen-compute"),
         EngineHandle(e2, "H20", "gen-bandwidth")],
        hw_affinity={"frozenlake": "H800", "webshop": "H800",
                     "swe": "H800", "math": "H20", "game": "H20",
                     "default": "H20"})

    t0 = time.time()
    with LiveRLRunner(
            RunnerConfig(batch_size=args.batch, group_size=args.group,
                         alpha=args.alpha, mode=args.mode,
                         tasks=tuple(args.tasks.split(",")),
                         max_new_tokens=args.max_new_tokens),
            proxy, state, step_fn, ServerlessPlatform(),
            format_bonus_reward, seq_len=640) as runner:
        for h in runner.run_steps(args.steps):
            print(f"step {h.step:3d}  loss {h.loss:+.4f}  "
                  f"reward {h.reward_mean:+.3f}  wall {h.wall_s:5.1f}s  "
                  f"ovl {h.decode_during_train:4d}  "
                  f"evicted {h.evicted}  aborted {h.aborted}")
        stats = runner.proxy.stats()
        print(f"\ndone in {time.time() - t0:.0f}s; routed by pool: "
              f"{stats['routed_by_pool']}; serverless reward calls: "
              f"{runner.serverless.stats.invocations}; {_weight_syncs(runner)}")


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
