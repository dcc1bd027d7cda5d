"""``device.idle_share``, in the Qwen3-Next cell."""
from benchmarks.chip import spec

read = spec.reader("device.idle_share")
