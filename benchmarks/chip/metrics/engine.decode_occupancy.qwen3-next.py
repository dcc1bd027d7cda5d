"""``engine.decode_occupancy``, in the Qwen3-Next cell."""
from benchmarks.chip import spec

read = spec.reader("engine.decode_occupancy")
