"""Tokens the trainer consumed in the window's steps over window seconds:
``traj_tokens_per_s``, read per layer where the weight-sync barrier makes
it too unsteady to hold to a bound."""


def read(run):
    return sum(map(sum, run.lengths)) / run.window_s if run.lengths else None
