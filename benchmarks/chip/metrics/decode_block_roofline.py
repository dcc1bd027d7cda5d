"""Least time of the window's decode blocks (``work.decode_work``) over
the device time of the engine's ``_decode_block`` program."""
from benchmarks.chip import work

PROGRAM = "jit__decode_block"


def read(run):
    p = (run.trace or {}).get("programs", {}).get(PROGRAM)
    if not p or p["seconds"] <= 0 or not run.peaks:
        return None
    w = work.decode_work(run.shapes, run.counters["decode_dispatches"],
                         run.plane["steps_per_dispatch"],
                         run.counters["decode_tokens"])
    return 100.0 * work.least_seconds(w, run.peaks) / p["seconds"]
