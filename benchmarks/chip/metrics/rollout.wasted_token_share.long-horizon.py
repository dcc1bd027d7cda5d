"""``rollout.wasted_token_share``, in the long-horizon cells, where it
moves the trajectory latency rather than the step time or the token
rate."""
from benchmarks.chip import spec

read = spec.reader("rollout.wasted_token_share")
