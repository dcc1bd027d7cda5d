"""``decode_block_roofline``, in the cells where it moves the trajectory latency
rather than the step time or the token rate."""
from benchmarks.chip import spec

read = spec.reader("decode_block_roofline")
