"""Seconds the process spent tracing, lowering and compiling (or reading
back from the persistent cache) programs before the window: the launch
layer's share of ``setup_s``."""


def read(run):
    return run.compile["setup_seconds"]
