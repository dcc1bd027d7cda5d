"""``rollout.wasted_token_share``, in the Qwen3-Next cell."""
from benchmarks.chip import spec

read = spec.reader("rollout.wasted_token_share")
