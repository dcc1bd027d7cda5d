"""Readings that set the limits of the comparison that decides ``correct``.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \\
        [--seconds 2] [--faults stale,token,unchanged,half]

For each seed, one run of the cell in this process (set-up, a short
window, the check) prints the program's compared numbers (the lower
readings) and the control's: the reference computed in float8 put in the
program's place, against the float32 reference, on the same batches and
turns (the upper readings). With ``--faults`` each fault of ``faults.py``
in turn is planted under the timed path, run on every seed and removed,
and only the program's numbers are printed. The benchmark's own runs
never run the control. One JSON line per seed and fault.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def run_one(cell, seed: int, seconds: float, fault, log):
    from benchmarks.chip import check, harness
    t0 = time.monotonic()
    r = harness.run(cell, seed, seconds, False, T_PROCESS, log)
    out = {"seed": seed, "fault": fault, "program": r.readings,
           "v0_turns": len(r.program.v0_turns),
           "v0_tokens": sum(len(t[2]) for t in r.program.v0_turns)}
    if not fault:
        t1 = time.monotonic()
        out["control"] = check.control(cell.config, seed, r.batches,
                                       r.program)
        out["control_s"] = time.monotonic() - t1
    out["run_s"] = time.monotonic() - t0
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)

    from benchmarks.chip import faults, harness, spec
    from repro.launch.compile_cache import enable_compile_cache
    cell = spec.cell(spec.load_benchmark(), args.workload)
    enable_compile_cache(1)
    log = harness.CompileLog()
    seeds = [int(s) for s in args.seeds.split(",")]
    for fault in args.faults.split(",") if args.faults else [None]:
        remove = faults.plant(fault, cell.config["vocab_size"]) \
            if fault else None
        try:
            for seed in seeds:
                run_one(cell, seed, args.seconds, fault, log)
        finally:
            if remove:
                remove()
    return 0


if __name__ == "__main__":
    sys.exit(main())
