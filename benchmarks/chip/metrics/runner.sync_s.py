"""Mean seconds per step of the weight sync under the service lock:
suspend, pull, update with in-flight KV recompute, resume, barrier hook
(``StepMetrics.sync_s``, the ``repro.runner.sync`` span). A program
without the field reads nothing."""


def read(run):
    syncs = [getattr(s, "sync_s", None) for s in run.steps]
    if not syncs or None in syncs:
        return None
    return sum(syncs) / len(syncs)
