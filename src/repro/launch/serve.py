"""Serving launcher: ``python -m repro.launch.serve --arch tiny --prompt ...``

Runs the continuous-batching engine on the local devices, optionally with
two affinity-routed pools. With ``--pd-disagg`` the data plane is split
into a prefill-role engine (compute pool) and a decode-role engine
(bandwidth pool) with a live KV-cache handoff between them (§6.3).
``--devices-per-engine`` runs each engine tensor-parallel over a disjoint
group of this process's devices (on CPU, expose them with ``XLA_FLAGS``).
"""
from __future__ import annotations

import argparse
import threading
import time

import jax

from repro.configs import get_config
from repro.core import (EngineHandle, LLMProxy, RebalancerConfig,
                        ResourceManager, build_pd_proxy, parse_pools)
from repro.core.proxy import format_placement_row
from repro.data.tokenizer import TOKENIZER
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.rl.engine import GenRequest, InferenceEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--prompt", action="append", default=None)
    ap.add_argument("--max-new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--pd-disagg", action="store_true",
                    help="split prefill/decode across two engine pools "
                         "with live KV-cache handoff (§6.3)")
    ap.add_argument("--pools", default=None, metavar="SPEC",
                    help="heterogeneous device inventory, e.g. "
                         "'H800:8,H20:8'; engines acquire device groups "
                         "through the ResourceManager")
    ap.add_argument("--affinity", action="store_true",
                    help="role-affine placement (prefill -> compute-class, "
                         "decode -> bandwidth-class pools, §5.2) plus the "
                         "dynamic prefill<->decode rebalancer; implies "
                         "--pd-disagg and requires --pools")
    ap.add_argument("--n-prefill", type=int, default=1)
    ap.add_argument("--n-decode", type=int, default=1)
    ap.add_argument("--devices-per-engine", type=int, default=1,
                    metavar="N",
                    help="TP group size: each engine runs sharded over a "
                         "disjoint group of N local devices (on CPU, "
                         "expose devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--prefill-devices-per-engine", type=int, default=None,
                    metavar="N",
                    help="per-role override of --devices-per-engine for "
                         "prefill engines (PD planes can size roles "
                         "independently, e.g. prefill 2 / decode 4)")
    ap.add_argument("--decode-devices-per-engine", type=int, default=None,
                    metavar="N",
                    help="per-role override of --devices-per-engine for "
                         "decode engines")
    ap.add_argument("--steps-per-dispatch", type=int, default=8,
                    metavar="K",
                    help="decode macro-step size: K scanned decode steps "
                         "per jit dispatch with on-device stop masking "
                         "(amortizes dispatch overhead K-fold; ADD/ABORT "
                         "latency is bounded by one macro-step, so lower "
                         "K for latency-sensitive serving; 1 = legacy "
                         "single-step dispatch)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV decode plane: shared page pool + "
                         "radix prefix cache (redundant prompts fork "
                         "their prefix instead of re-prefilling) + "
                         "compacted decode dispatch that skips idle "
                         "slots; greedy output is byte-identical to the "
                         "dense cache")
    ap.add_argument("--page-size", type=int, default=16, metavar="T",
                    help="tokens per KV page under --paged")
    ap.add_argument("--service", action="store_true",
                    help="serve through the multi-tenant RolloutService "
                         "(Rollout-as-a-Service): prompts are submitted "
                         "as streaming jobs and tokens print as the "
                         "engines emit them, while the service thread "
                         "owns the pump loop")
    ap.add_argument("--async-pump", action="store_true",
                    help="pump the engines from a background thread while "
                         "requests are submitted concurrently (the live "
                         "runner's producer/consumer shape)")
    ap.add_argument("--failure-rate", type=float, default=0.0, metavar="P",
                    help="fault-tolerance demo (§8): crash the busiest "
                         "engine after ~1/P pumps and recover its "
                         "in-flight requests from the periodic KV-slot "
                         "snapshot (snapshot-covered requests resume "
                         "mid-decode; the rest re-prefill)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    metavar="PORT",
                    help="serve Prometheus text metrics at "
                         "http://127.0.0.1:PORT/metrics (0 = ephemeral "
                         "port; watch live with "
                         "python -m repro.obs.dashboard --url ...)")
    ap.add_argument("--watchdog", action="store_true",
                    help="heartbeat watchdog (§8): hard-kill an engine "
                         "whose beat goes silent while work is queued, "
                         "then resume its requests from the periodic "
                         "KV-slot snapshot (uncovered ones re-prefill); "
                         "requires --async-pump")
    ap.add_argument("--watchdog-deadline", type=float, default=2.0,
                    metavar="S", help="stall deadline in seconds")
    args = ap.parse_args(argv)
    if args.failure_rate > 0 and args.async_pump:
        ap.error("--failure-rate drives the synchronous pump loop; drop "
                 "--async-pump")
    if args.service and (args.async_pump or args.failure_rate > 0):
        ap.error("--service owns the pump loop; drop --async-pump / "
                 "--failure-rate")
    if args.watchdog and not args.async_pump:
        ap.error("--watchdog recovers the background pump path; add "
                 "--async-pump (training uses repro.launch.train "
                 "--watchdog)")

    dpe = args.devices_per_engine
    pre_dpe = args.prefill_devices_per_engine or dpe
    dec_dpe = args.decode_devices_per_engine or dpe
    enable_compile_cache(max(dpe, pre_dpe, dec_dpe))
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    if args.affinity and not args.pools:
        ap.error("--affinity requires --pools (e.g. --pools H800:2,H20:2)")
    if args.pools and not (args.pd_disagg or args.affinity):
        ap.error("--pools only takes effect on the disaggregated plane; "
                 "add --pd-disagg or --affinity")
    rm = ResourceManager(parse_pools(args.pools)) if args.pools else None
    if args.pd_disagg or args.affinity:
        proxy = build_pd_proxy(
            model, params, max_slots=args.slots, max_len=1024,
            n_prefill=args.n_prefill, n_decode=args.n_decode,
            resource_manager=rm,
            rebalancer=RebalancerConfig() if args.affinity else None,
            steps_per_dispatch=args.steps_per_dispatch,
            prefill_devices_per_engine=pre_dpe,
            decode_devices_per_engine=dec_dpe,
            paged=args.paged, page_size=args.page_size)
        if args.affinity:
            for row in proxy.placement_report():
                print("placement: " + format_placement_row(row))
    else:
        mesh = None
        if dpe > 1:
            from repro.launch.mesh import (allocate_engine_devices,
                                           make_group_mesh)
            mesh = make_group_mesh(allocate_engine_devices([dpe])[0])
        eng = InferenceEngine(model, params, max_slots=args.slots,
                              max_len=1024,
                              steps_per_dispatch=args.steps_per_dispatch,
                              mesh=mesh, paged=args.paged,
                              page_size=args.page_size)
        proxy = LLMProxy([EngineHandle(eng, "local")])

    prompts = args.prompt or ["the agent moves ", "reward comes from "]
    reg = mserver = None
    if args.metrics_port is not None:
        from repro.obs import (MetricsRegistry, MetricsServer,
                               instrument_proxy)
        reg = MetricsRegistry()
        instrument_proxy(reg, proxy)
        mserver = MetricsServer(reg, port=args.metrics_port).start()
        print(f"metrics: {mserver.url}")
    if args.service:
        # Rollout-as-a-Service: the service thread owns the pump loop;
        # this thread is an ordinary streaming client
        from repro.serve import RolloutJob, RolloutService
        with RolloutService(proxy) as svc:
            svc.register_tenant("cli")
            if reg is not None:
                from repro.obs import instrument_service
                instrument_service(reg, svc)
            svc.start()
            tickets = [
                (p, svc.submit("cli", RolloutJob(
                    kind="prompt",
                    prompt=TOKENIZER.encode(p, bos=True),
                    max_new_tokens=args.max_new_tokens,
                    temperature=args.temperature)))
                for p in prompts]
            for p, tk in tickets:
                print(f"[{tk.job_id}] {p!r} -> ", end="", flush=True)
                for chunk in tk.stream:      # prints as the engines emit
                    print(TOKENIZER.decode(chunk.tokens), end="",
                          flush=True)
                print(f"  ({tk.wait(timeout=60)})")
        if mserver is not None:
            mserver.close()
        proxy.release_bindings()
        return
    results = []
    requests = {}
    if args.failure_rate > 0:
        # synchronous pump loop with one injected engine crash + recovery
        for i, p in enumerate(prompts):
            req = GenRequest(request_id=f"r{i}",
                             prompt=TOKENIZER.encode(p, bos=True),
                             max_new_tokens=args.max_new_tokens,
                             temperature=args.temperature)
            requests[req.request_id] = req
            proxy.submit(req, callback=results.append)
        kill_after = max(3, int(round(1.0 / args.failure_rate)))
        snap_slots = {}
        pumps, killed = 0, False
        while proxy.busy:
            if not killed and pumps % 2 == 0:
                # periodic KV-slot snapshot (the serving-side analogue of
                # the runner's barrier snapshot); requests the snapshot
                # misses simply re-prefill at recovery
                snap_slots = {hf.request.request_id: hf
                              for h in proxy.handles
                              for hf in h.engine.snapshot_slots()}
            proxy.pump()
            pumps += 1
            if not killed and pumps >= kill_after:
                victim = max(proxy.handles,
                             key=lambda h: h.engine.inflight_decode_tokens)
                lost = proxy.requests_on(victim)
                victim.engine.crash()
                resumed = resubmitted = 0
                for rid in lost:
                    hf = snap_slots.get(rid)
                    if hf is not None:
                        proxy.reinject(hf)     # callback still registered
                        resumed += 1
                    else:
                        proxy.drop_routes([rid])
                        proxy.submit(requests[rid],
                                     callback=results.append)
                        resubmitted += 1
                print(f"ft: crashed engine {victim.name or victim.pool} "
                      f"after {pumps} pumps — {len(lost)} in-flight lost, "
                      f"{resumed} resumed from snapshot, "
                      f"{resubmitted} re-prefilled")
                killed = True
    elif args.async_pump:
        # producer/consumer serving: a dedicated thread pumps while this
        # thread keeps submitting — the engine command queues and the
        # proxy route table absorb the concurrency
        stop = threading.Event()
        pump_error = []
        snap_lock = threading.Lock()
        snap_slots = {}                 # guarded by snap_lock

        def pump_loop():
            try:
                pumps = 0
                while not stop.is_set():
                    if args.watchdog and pumps % 2 == 0:
                        # periodic KV-slot snapshot, same idiom as the
                        # --failure-rate demo: watchdog-recovered
                        # requests resume mid-decode when covered
                        snap = {hf.request.request_id: hf
                                for h in proxy.handles
                                for hf in h.engine.snapshot_slots()}
                        with snap_lock:
                            snap_slots.clear()
                            snap_slots.update(snap)
                    if proxy.pump() == 0:
                        time.sleep(0.001)
                    pumps += 1
            except BaseException as e:      # surfaced by the wait loop
                pump_error.append(e)

        pump_thread = threading.Thread(target=pump_loop, daemon=True)
        pump_thread.start()
    if args.failure_rate <= 0:
        for i, p in enumerate(prompts):
            req = GenRequest(request_id=f"r{i}",
                             prompt=TOKENIZER.encode(p, bos=True),
                             max_new_tokens=args.max_new_tokens,
                             temperature=args.temperature)
            requests[req.request_id] = req
            proxy.submit(req, callback=results.append)
    wdog = None
    if args.async_pump:
        if args.watchdog:
            from repro.obs import Watchdog, watch_engines

            def recover(handle):
                """Serving-side hung-engine recovery: hard-kill (the
                lock-free SIGKILL analogue, honored as the wedged step
                unwinds), wait for the replacement process, then resume
                snapshot-covered requests and re-prefill the rest."""
                eng = handle.engine
                lost = proxy.requests_on(handle)
                c0 = eng.crashes
                eng.hard_kill()
                deadline = time.monotonic() + 30
                while eng.crashes == c0:
                    if time.monotonic() > deadline:
                        raise RuntimeError("hard-killed engine never "
                                           "came back")
                    time.sleep(0.005)
                with snap_lock:
                    snap = dict(snap_slots)
                resumed = resubmitted = 0
                for rid in lost:
                    hf = snap.get(rid)
                    if hf is not None:
                        proxy.reinject(hf)   # callback still registered
                        resumed += 1
                    else:
                        proxy.drop_routes([rid])
                        proxy.submit(requests[rid],
                                     callback=results.append)
                        resubmitted += 1
                print(f"watchdog: killed hung engine "
                      f"{handle.name or handle.pool} — {len(lost)} "
                      f"in-flight, {resumed} resumed from snapshot, "
                      f"{resubmitted} re-prefilled")

            wdog = Watchdog(deadline_s=args.watchdog_deadline,
                            registry=reg)
            watch_engines(wdog, proxy, recover=recover)
            wdog.start()
        while len(results) < len(prompts):
            if pump_error:
                raise RuntimeError("pump thread died") from pump_error[0]
            time.sleep(0.005)
        stop.set()
        pump_thread.join()
        if wdog is not None:
            wdog.close()
    else:
        while proxy.busy:
            proxy.pump()
    for r in sorted(results, key=lambda r: r.request_id):
        i = int(r.request_id[1:])
        print(f"[{r.request_id}] {prompts[i]!r} -> "
              f"{TOKENIZER.decode(r.tokens)!r}")
    if args.pd_disagg or args.affinity:
        stats = proxy.stats()
        for e in stats["engines"]:
            print(f"pool={e['pool']} role={e['role']} "
                  f"prefill_tokens={e['prefill_tokens']} "
                  f"decode_tokens={e['decode_tokens']}")
        if args.affinity:
            print(f"role_switches={stats['role_switches']} "
                  f"switch_migrations={stats['switch_migrations']}")
    if mserver is not None:
        mserver.close()
    proxy.release_bindings()


if __name__ == "__main__":
    main()
