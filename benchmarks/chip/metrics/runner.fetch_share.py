"""Share of the window the trainer spent blocked on the sample buffer
(``StepMetrics.fetch_s``, step (1) of the runner's protocol)."""


def read(run):
    if not run.steps:
        return None
    return 100.0 * sum(s.fetch_s for s in run.steps) / run.window_s
