"""Least time of the window's decode blocks at Qwen3-Next's shapes
(``work_qwen3_next.decode_work``: the weights but the routed experts' once
a scanned step, each held expert some row routed to once, each emitted
token's DeltaNet states and K/V) over the device time of the engine's
``_decode_block`` program. A program without the expert counters in its
``StepMetrics`` reads nothing."""
from benchmarks.chip import work_qwen3_next as W

PROGRAM = "jit__decode_block"


def read(run):
    p = (run.trace or {}).get("programs", {}).get(PROGRAM)
    moe = [(getattr(s, "moe_decode_experts_touched", None),
            getattr(s, "moe_decode_tokens_here", None)) for s in run.steps]
    if (not p or p["seconds"] <= 0 or not run.peaks or not moe
            or any(None in m for m in moe)):
        return None
    w = W.decode_work(W.Shapes.from_config(run.cell.config),
                      run.counters["decode_dispatches"],
                      run.plane["steps_per_dispatch"],
                      run.counters["decode_tokens"],
                      touched=sum(t for t, _ in moe),
                      assignments=sum(a for _, a in moe))
    return 100.0 * W.least_seconds(w, run.peaks) / p["seconds"]
