"""``runner.sync_s``, in the Qwen3-Next cell."""
from benchmarks.chip import spec

read = spec.reader("runner.sync_s")
