"""Least time of the window's prefills (``work.prefill_work``: the
admitted prompts' real tokens, weights read once per call) over the device
time of the engine's ``_prefill_into_slot`` program."""
from benchmarks.chip import work

PROGRAM = "jit__prefill_into_slot"


def read(run):
    p = (run.trace or {}).get("programs", {}).get(PROGRAM)
    if not p or p["seconds"] <= 0 or not run.peaks:
        return None
    w = work.prefill_work(run.shapes, p["calls"],
                          run.counters["prefill_tokens"])
    return 100.0 * work.least_seconds(w, run.peaks) / p["seconds"]
