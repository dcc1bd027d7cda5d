"""The whole loop's share of the chips' peak FLOP/s over the window: the
FLOPs the GRPO steps, the admission prefills and the decoded tokens
require (``work``; KV recomputes at weight sync do not count) over window
seconds x chips x peak."""
from benchmarks.chip import work


def read(run):
    if not run.peaks or not run.lengths:
        return None
    s = run.shapes
    rows = [n for step in run.lengths for n in step]
    flops = (work.train_work(s, rows)["flops"]
             + 2.0 * s.layers * s.layer_matrix_params
             * run.counters["prefill_tokens"]
             + work.decode_work(s, 0, 0, run.counters["decode_tokens"])
             ["flops"])
    return 100.0 * flops / (run.window_s * run.chips * run.peaks["flops"])
