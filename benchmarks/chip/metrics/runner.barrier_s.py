"""Mean seconds per step in the weight-sync barrier: push await, suspend,
weight update with in-flight KV recompute, resume
(``StepMetrics.barrier_s``)."""


def read(run):
    if not run.steps:
        return None
    return sum(s.barrier_s for s in run.steps) / len(run.steps)
