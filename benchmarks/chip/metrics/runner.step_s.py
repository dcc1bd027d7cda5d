"""Window seconds over the train steps completed in it: ``step_s``, read
per layer where the weight-sync barrier makes it too unsteady to hold to
a bound."""


def read(run):
    return run.window_s / len(run.steps) if run.steps else None
