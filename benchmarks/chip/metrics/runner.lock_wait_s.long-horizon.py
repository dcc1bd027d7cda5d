"""``runner.lock_wait_s``, in the long-horizon cells, where it moves the
trajectory latency rather than the step time or the token rate."""
from benchmarks.chip import spec

read = spec.reader("runner.lock_wait_s")
