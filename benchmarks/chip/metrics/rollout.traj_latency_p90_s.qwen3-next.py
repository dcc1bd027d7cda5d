"""``rollout.traj_latency_p90_s``, in the Qwen3-Next cell."""
from benchmarks.chip import spec

read = spec.reader("rollout.traj_latency_p90_s")
