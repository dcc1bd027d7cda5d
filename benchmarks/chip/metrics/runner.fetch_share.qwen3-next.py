"""``runner.fetch_share``, in the Qwen3-Next cell."""
from benchmarks.chip import spec

read = spec.reader("runner.fetch_share")
