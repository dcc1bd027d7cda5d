"""Share of the window in which the rollout thread held the service lock
without waiting on the chip: the sum over the window's steps of
``StepMetrics.rollout_host_s`` (``repro.service.tick`` seconds less the
engines' ``repro.engine.prefill`` / ``.decode`` seconds). A program
without the field reads nothing."""


def read(run):
    host = [getattr(s, "rollout_host_s", None) for s in run.steps]
    if not host or None in host:
        return None
    return 100.0 * sum(host) / run.window_s
