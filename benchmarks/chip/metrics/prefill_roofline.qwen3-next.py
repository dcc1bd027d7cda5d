"""Least time of the window's prefills at Qwen3-Next's shapes
(``work_qwen3_next.prefill_work``: the admitted prompts' real tokens
through every matrix but the routed experts', those weights read once a
call, each call's DeltaNet states and the prompt's K/V written) over the
device time of the engine's ``_prefill_into_slot`` program, which pads a
prompt to its bucket and masks the pad out of the recurrence."""
from benchmarks.chip import work_qwen3_next as W

PROGRAM = "jit__prefill_into_slot"


def read(run):
    p = (run.trace or {}).get("programs", {}).get(PROGRAM)
    if not p or p["seconds"] <= 0 or not run.peaks:
        return None
    w = W.prefill_work(W.Shapes.from_config(run.cell.config), p["calls"],
                       run.counters["prefill_tokens"])
    return 100.0 * W.least_seconds(w, run.peaks) / p["seconds"]
