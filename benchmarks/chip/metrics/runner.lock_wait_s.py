"""Mean seconds per step the trainer waited for the service lock at the
weight-sync barrier (``StepMetrics.lock_wait_s``, the
``repro.runner.lock_wait`` span): the rollout thread's ticks holding the
lock. A program without the field reads nothing."""


def read(run):
    waits = [getattr(s, "lock_wait_s", None) for s in run.steps]
    if not waits or None in waits:
        return None
    return sum(waits) / len(waits)
