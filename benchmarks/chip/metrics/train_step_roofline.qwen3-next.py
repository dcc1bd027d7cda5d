"""Least time of the window's GRPO steps at Qwen3-Next's shapes
(``work_qwen3_next.train_work`` over the rows' real lengths and the
steps' assignments to held experts) over the device time of the jitted
``train_step``. A program without the expert counters reads nothing."""
from benchmarks.chip import harness
from benchmarks.chip import work_qwen3_next as W

PROGRAM = "jit_train_step"


def read(run):
    p = (run.trace or {}).get("programs", {}).get(PROGRAM)
    here = [getattr(s, "moe_train_tokens_here", None) for s in run.steps]
    if (not p or p["seconds"] <= 0 or not run.peaks or not run.lengths
            or not here or None in here):
        return None
    rows = [n for step in run.lengths for n in step]
    w = W.train_work(W.Shapes.from_config(run.cell.config), rows,
                     steps=len(run.lengths), assignments=sum(here),
                     padded_tokens=len(rows) * harness.SEQ_LEN)
    return 100.0 * W.least_seconds(w, run.peaks) / p["seconds"]
