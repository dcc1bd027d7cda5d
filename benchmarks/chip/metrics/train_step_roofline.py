"""Least time of the window's GRPO steps (``work.train_work`` over the
rows' real lengths) over the device time of the jitted ``train_step``."""
from benchmarks.chip import work

PROGRAM = "jit_train_step"


def read(run):
    p = (run.trace or {}).get("programs", {}).get(PROGRAM)
    if not p or p["seconds"] <= 0 or not run.peaks or not run.lengths:
        return None
    rows = [n for step in run.lengths for n in step]
    w = work.train_work(run.shapes, rows, steps=len(run.lengths))
    return 100.0 * work.least_seconds(w, run.peaks) / p["seconds"]
