"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

Live mode (default): runs the full RollArt agentic-RL pipeline with real
compute on the local devices (``--reduced`` gives the smoke-scale variant
of an arch; tests and CPU runs use ``JAX_PLATFORMS=cpu``). With ``--lm``
it runs plain LM pretraining instead. ``--devices-per-engine`` and
``--pd-disagg`` place rollout engines on disjoint device groups of this
process; ``chip_smoke.py`` drives this same plane on a TPU through
:func:`build_runner`.
"""
from __future__ import annotations

import argparse

import jax

from repro.checkpoint import checkpointer as CK
from repro.configs import get_config
from repro.core import (DEFAULT_TASKS, EngineHandle, LiveRLRunner, LLMProxy,
                        RebalancerConfig, ResourceManager, RunnerConfig,
                        ServerlessPlatform, build_pd_proxy, parse_pools)
from repro.core.hardware import device_pool
from repro.core.proxy import format_placement_row, format_switch_event
from repro.ft import FTConfig, FTSupervisor, restore_latest
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.rewards.rule_based import REWARD_FNS
from repro.rl.engine import InferenceEngine
from repro.rl.trainer import (default_optimizer, init_train_state,
                              make_grpo_train_step, make_lm_train_step)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale variant of the arch")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--group", type=int, default=4)
    ap.add_argument("--alpha", type=int, default=1)
    ap.add_argument("--mode", default="rollart",
                    choices=["rollart", "areal", "one_off", "sync",
                             "sync_plus"],
                    help="rollart/areal/one_off run rollout on a "
                         "background worker thread, overlapping train_step")
    ap.add_argument("--tasks", default=",".join(DEFAULT_TASKS),
                    help="comma-separated multi-task mix (default includes "
                         "the long-tail swe/webshop environments)")
    ap.add_argument("--task-weights", default=None,
                    help="comma-separated sampling weights matching --tasks "
                         "(default: weighted mix for the default tasks, "
                         "uniform for a custom task set)")
    ap.add_argument("--reward", default="format_bonus",
                    choices=sorted(REWARD_FNS))
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--lm", action="store_true", help="LM pretrain instead "
                    "of agentic RL")
    ap.add_argument("--pd-disagg", action="store_true",
                    help="rollout on disaggregated prefill/decode engine "
                         "pools with live KV handoff (§6.3)")
    ap.add_argument("--pools", default=None, metavar="SPEC",
                    help="heterogeneous rollout device inventory, e.g. "
                         "'H800:8,H20:8' (ResourceManager-backed)")
    ap.add_argument("--affinity", action="store_true",
                    help="role-affine placement (prefill -> compute-class, "
                         "decode -> bandwidth-class, §5.2) plus the dynamic "
                         "prefill<->decode rebalancer; implies --pd-disagg "
                         "and requires --pools")
    ap.add_argument("--n-prefill", type=int, default=None,
                    help="prefill-role engines when disaggregated "
                         "(default 1; 2 with --affinity, so the "
                         "rebalancer has room to switch one)")
    ap.add_argument("--n-decode", type=int, default=1)
    ap.add_argument("--devices-per-engine", type=int, default=1,
                    metavar="N",
                    help="TP group size: each rollout engine runs sharded "
                         "over a disjoint group of N local devices; weight "
                         "sync then moves per-shard chunks through the "
                         "store (on CPU expose devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--prefill-devices-per-engine", type=int, default=None,
                    metavar="N",
                    help="per-role override of --devices-per-engine for "
                         "prefill engines on the disaggregated plane")
    ap.add_argument("--decode-devices-per-engine", type=int, default=None,
                    metavar="N",
                    help="per-role override of --devices-per-engine for "
                         "decode engines on the disaggregated plane")
    ap.add_argument("--steps-per-dispatch", type=int, default=8,
                    metavar="K",
                    help="decode macro-step size: K scanned decode steps "
                         "per jit dispatch (device-resident decode; abort/"
                         "staleness enforcement latency is bounded by one "
                         "macro-step — lower K to tighten it, 1 = legacy "
                         "single-step dispatch)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-rollouts", action="store_true",
                    help="fault tolerance (§8): snapshot the FULL rollout "
                         "plane (env managers, engine KV slots, buffered "
                         "samples, pending rewards) alongside the train "
                         "state at every weight-sync barrier; requires "
                         "--ckpt")
    ap.add_argument("--snapshot-every", type=int, default=1, metavar="N",
                    help="barrier cadence of the rollout snapshots")
    ap.add_argument("--keep-last", type=int, default=3,
                    help="retained checkpoint/snapshot pairs")
    ap.add_argument("--failure-rate", type=float, default=0.0,
                    metavar="P",
                    help="inject a random env/engine/reward failure with "
                         "probability P per iteration (paper §8 observes "
                         "~0.1) and recover it under the FT supervisor")
    ap.add_argument("--restore", action="store_true",
                    help="resume from the latest intact train+rollout "
                         "checkpoint pair under --ckpt (trainer-failure "
                         "restart; corrupt pairs fall back to step N-1)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    metavar="PORT",
                    help="serve Prometheus text metrics for the whole "
                         "training stack at http://127.0.0.1:PORT/metrics "
                         "(0 = ephemeral port; watch live with "
                         "python -m repro.obs.dashboard --url ...)")
    ap.add_argument("--watchdog", action="store_true",
                    help="heartbeat watchdog (§8): detect silently hung "
                         "engines / pump loop (beat silent past the "
                         "deadline while work is queued) and recover them "
                         "through the FT supervisor")
    ap.add_argument("--watchdog-deadline", type=float, default=5.0,
                    metavar="S", help="stall deadline in seconds")
    return ap


def build_runner(args, model, state, step) -> LiveRLRunner:
    """The live RL plane over ``state``: rollout engines (one local
    engine, a TP group, or a disaggregated prefill/decode pool) behind an
    LLMProxy, driven by a LiveRLRunner that trains with ``step``. Also the
    trainer-restart hook (``restore_latest`` rebuilds the plane through
    it). Engines without a ResourceManager binding are labelled with the
    hardware they run on."""
    pools = parse_pools(args.pools) if args.pools else None
    pd = args.pd_disagg or args.affinity
    n_prefill = args.n_prefill or (2 if args.affinity else 1)
    weights = (tuple(float(w) for w in args.task_weights.split(","))
               if args.task_weights else None)
    dpe = args.devices_per_engine
    local = device_pool(jax.devices()[0])
    rm = ResourceManager(pools) if pools else None
    if pd:
        proxy = build_pd_proxy(
            model, state.params, max_slots=8, max_len=640,
            n_prefill=n_prefill, n_decode=args.n_decode,
            resource_manager=rm,
            rebalancer=RebalancerConfig() if args.affinity else None,
            steps_per_dispatch=args.steps_per_dispatch,
            prefill_devices_per_engine=args.prefill_devices_per_engine or dpe,
            decode_devices_per_engine=args.decode_devices_per_engine or dpe,
            prefill_pool=local, decode_pool=local)
    else:
        mesh = None
        if dpe > 1:
            from repro.launch.mesh import (allocate_engine_devices,
                                           make_group_mesh)
            mesh = make_group_mesh(allocate_engine_devices([dpe])[0])
        eng = InferenceEngine(
            model, state.params, max_slots=8, max_len=640,
            steps_per_dispatch=args.steps_per_dispatch, mesh=mesh)
        proxy = LLMProxy([EngineHandle(eng, local)])
    return LiveRLRunner(
        RunnerConfig(batch_size=args.batch, group_size=args.group,
                     alpha=args.alpha, mode=args.mode,
                     tasks=tuple(args.tasks.split(",")),
                     task_weights=weights,
                     pd_disagg=pd, pools=pools,
                     affinity=args.affinity,
                     steps_per_dispatch=args.steps_per_dispatch),
        proxy, state, step, ServerlessPlatform(),
        REWARD_FNS[args.reward], seq_len=640)


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    if (args.ckpt_rollouts or args.restore) and not args.ckpt:
        ap.error("--ckpt-rollouts/--restore need --ckpt DIR")

    dpe = args.devices_per_engine
    enable_compile_cache(max(dpe, args.prefill_devices_per_engine or dpe,
                             args.decode_devices_per_engine or dpe))
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg, remat=False)
    opt = default_optimizer(args.lr)
    state = init_train_state(model, jax.random.PRNGKey(0), opt)

    if args.lm:
        from repro.data.pipeline import lm_batches
        from repro.data.tokenizer import TOKENIZER
        import jax.numpy as jnp
        step = jax.jit(make_lm_train_step(model, opt))
        for i, batch in enumerate(lm_batches(TOKENIZER, 128, args.batch,
                                             args.steps)):
            state, m = step(state, {k: jnp.asarray(v)
                                    for k, v in batch.items()})
            print(f"step {i} loss {float(m['loss']):.4f}")
    else:
        step = jax.jit(make_grpo_train_step(model, opt))
        if args.affinity and not args.pools:
            ap.error("--affinity requires --pools "
                     "(e.g. --pools H800:2,H20:2)")
        if args.pools and not (args.pd_disagg or args.affinity):
            ap.error("--pools only takes effect on the disaggregated "
                     "plane; add --pd-disagg or --affinity")

        if args.restore:
            runner, start = restore_latest(
                args.ckpt, state,
                lambda st: build_runner(args, model, st, step))
            print(f"restored paired checkpoint at step {start}")
        else:
            runner = build_runner(args, model, state, step)
        # --watchdog needs an FT supervisor to recover through, even
        # without checkpointing/injection configured
        use_ft = (args.ckpt_rollouts or args.failure_rate > 0
                  or args.watchdog)
        sup = None
        mserver = wdog = reg = None
        with runner:
            if args.affinity:
                for row in runner.placement_report():
                    print("placement: " + format_placement_row(row))
            if use_ft:
                sup = FTSupervisor(
                    runner,
                    FTConfig(snapshot_every=args.snapshot_every,
                             failure_rate=args.failure_rate,
                             keep_last=args.keep_last),
                    ckpt_dir=args.ckpt if args.ckpt_rollouts else None)
            if args.metrics_port is not None:
                from repro.obs import (MetricsRegistry, MetricsServer,
                                       instrument_runner)
                reg = MetricsRegistry()
                instrument_runner(reg, runner)
                mserver = MetricsServer(reg,
                                        port=args.metrics_port).start()
                print(f"metrics: {mserver.url}")
            if args.watchdog:
                from repro.obs import (Watchdog, watch_engines,
                                       watch_env_managers, watch_service)
                wdog = Watchdog(deadline_s=args.watchdog_deadline,
                                registry=reg)
                watch_engines(wdog, runner.proxy,
                              recover=sup.recover_hung_engine)
                watch_service(wdog, runner.service)
                watch_env_managers(wdog, runner,
                                   recover=sup.recover_stalled_ems)
                wdog.start()
            try:
                if sup is not None:
                    hist = sup.run_steps(args.steps)
                else:
                    hist = runner.run_steps(args.steps)
            finally:
                if wdog is not None:
                    wdog.close()
                if mserver is not None:
                    mserver.close()
            for h in hist:
                d = h.to_dict()   # the stable export schema, verbatim
                print(f"step {d['step']} loss {d['loss']:.4f} "
                      f"reward {d['reward_mean']:.3f} "
                      f"wall {d['wall_s']:.1f}s "
                      f"(fetch {d['fetch_s']:.1f} "
                      f"barrier {d['barrier_s']:.2f} "
                      f"train {d['train_s']:.1f}) "
                      f"stale {d['staleness']} "
                      f"ovl_decode_toks {d['decode_during_train']}"
                      + (f" role_switches {d['role_switches']}"
                         if args.affinity else "")
                      + (f" deduped {d['deduped']}" if d['deduped']
                         else ""))
            if args.affinity:
                for ev in runner.proxy.switch_log:
                    print(format_switch_event(ev))
            state = runner.state
        if sup is not None:
            sup.close()
            for line in sup.log:
                print("ft: " + line)
        runner.proxy.release_bindings()
    if args.ckpt and not args.ckpt_rollouts:
        # with --ckpt-rollouts the supervisor already persisted paired
        # full-state checkpoints; a trailing params-only save would mix
        # tree structures in the same directory
        print("saved:", CK.save(args.ckpt, state.params,
                                step=int(state.version)))


if __name__ == "__main__":
    main()
