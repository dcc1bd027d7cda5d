"""Share of the decode block's token positions that emitted a token:
decode tokens over dispatches x steps per dispatch x slots, counted over
the window."""


def read(run):
    n = run.counters["decode_dispatches"]
    if n == 0:
        return None
    positions = n * run.plane["steps_per_dispatch"] * run.plane["slots"]
    return 100.0 * run.counters["decode_tokens"] / positions
