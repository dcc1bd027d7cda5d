"""Held experts that some emitting row routed to, as a share of held
experts x expert layers x scanned decode steps in the window: the part of
an every-held-expert decode that does needed work (its complement is
computed for nothing). A program without the counter reads nothing."""
from benchmarks.chip import work_qwen3_next as W


def read(run):
    touched = [getattr(s, "moe_decode_experts_touched", None)
               for s in run.steps]
    steps = (run.counters["decode_dispatches"]
             * run.plane["steps_per_dispatch"])
    if not touched or None in touched or steps <= 0:
        return None
    s = W.Shapes.from_config(run.cell.config)
    return 100.0 * sum(touched) / (s.experts * s.layers * steps)
