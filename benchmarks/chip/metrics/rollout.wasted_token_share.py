"""Policy tokens of the window's steps thrown away, as a share of the
tokens the engines decoded in the window: trajectories aborted before
scoring, with the in-flight request's part (``StepMetrics.aborted_tokens``),
and trajectories the buffer evicted as stale (``evicted_tokens``). A
program without the fields reads nothing."""


def read(run):
    wasted = [(getattr(s, "aborted_tokens", None),
               getattr(s, "evicted_tokens", None)) for s in run.steps]
    decoded = run.counters["decode_tokens"]
    if not wasted or decoded <= 0 or any(None in w for w in wasted):
        return None
    return 100.0 * sum(a + e for a, e in wasted) / decoded
