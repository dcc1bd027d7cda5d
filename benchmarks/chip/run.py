"""Chip benchmark of the live agentic-RL loop.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and
prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and,
traced, ``breakdown``), and last a ``checks`` object with each compared
number beside its limit; the same numbers end standard error. With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, from a profiler trace of the window.
JAX must find a TPU and as many chips as the cell asks for; otherwise the
run exits 2 and prints no result. The persistent compilation cache is
the program's own (``$JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.jax_cache``).
"""
import time

T_PROCESS = time.monotonic()   # set-up is measured from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# libtpu otherwise writes its logs under /tmp, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def end_to_end(r) -> dict:
    lat = sorted(r.latencies)
    p90 = statistics.quantiles(lat, n=10, method="inclusive")[-1] \
        if len(lat) >= 2 else None
    return {
        "setup_s": r.setup_s,
        "step_s": r.window_s / len(r.steps),
        "traj_tokens_per_s": sum(map(sum, r.lengths)) / r.window_s,
        "traj_latency_p90_s": p90,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.chip import spec
    cell = spec.cell(spec.load_benchmark(), args.workload)
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu" or len(devices) < cell.chips:
        print(f"benchmark: JAX found {len(devices)} device(s) of platform "
              f"{d0.platform!r} ({d0.device_kind}); cell {cell.name} needs "
              f"{cell.chips} TPU chip(s); refusing to run", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    from benchmarks.chip import check, harness
    cache = enable_compile_cache(1)
    log = harness.CompileLog()
    r = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                    T_PROCESS, log)

    print(f"cache: {cache}; set-up compiled or loaded "
          f"{r.compile['setup_programs']} programs in "
          f"{r.compile['setup_seconds']!r} s; programs compiled inside the "
          f"window: {r.compile['window_programs']}", flush=True)
    print(f"window: {r.window_s!r} s, {len(r.steps)} train steps, "
          f"{len(r.latencies)} trajectories completed, {r.attempted} "
          f"started, {r.failed} failed, {r.aborted} aborted for staleness; "
          f"trained per task {r.trained_tasks}", flush=True)
    if r.latencies:
        lat = sorted(r.latencies)
        print(f"trajectory latency s: mean {statistics.fmean(lat)!r}, "
              f"median {statistics.median(lat)!r}, max {lat[-1]!r}",
              flush=True)
    print(f"readings against the reference: {json.dumps(r.readings)}",
          flush=True)
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": cell.chips, "memory_peak_bytes": r.memory_peak_bytes}
    out = {"correct": check.correct(r.compared), "attempted": r.attempted,
           "failed": r.failed}
    if args.trace:
        metrics = {}
        for m in cell.per_layer:
            v = spec.reader(m["name"])(r)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if r.trace is not None:
            device["busy_s"] = r.trace["busy_s"]
            device["window_s"] = r.trace["window_s"]
            out["breakdown"] = {"device_ops": r.trace["device_ops"],
                                "idle_gaps": r.trace["idle_gaps"]}
    else:
        vals = end_to_end(r)
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if vals.get(m["name"]) is not None}
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = r.compared
    for name, c in r.compared.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
