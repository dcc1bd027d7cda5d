"""The whole loop's share of the chip's peak FLOP/s over the window at
Qwen3-Next's shapes: the FLOPs the GRPO steps, the admission prefills
(dense part only) and the decoded tokens require (``work_qwen3_next``;
KV recomputes at weight sync do not count) over window seconds x chips x
peak. A program without the expert counters reads nothing."""
from benchmarks.chip import harness
from benchmarks.chip import work_qwen3_next as W


def read(run):
    train = [getattr(s, "moe_train_tokens_here", None) for s in run.steps]
    decode = [getattr(s, "moe_decode_tokens_here", None) for s in run.steps]
    if (not run.peaks or not run.lengths or not train
            or None in train + decode):
        return None
    s = W.Shapes.from_config(run.cell.config)
    rows = [n for step in run.lengths for n in step]
    flops = (W.train_work(s, rows, len(run.lengths), sum(train),
                          len(rows) * harness.SEQ_LEN)["flops"]
             + W.prefill_flops(s, run.counters["prefill_tokens"])
             + W.decode_work(s, 0, 0, run.counters["decode_tokens"], 0,
                             sum(decode))["flops"])
    return 100.0 * flops / (run.window_s * run.chips * run.peaks["flops"])
