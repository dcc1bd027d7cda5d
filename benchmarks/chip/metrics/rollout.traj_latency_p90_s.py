"""90th percentile of first request's submit to last request's finish,
over every trajectory that completed in the window: ``traj_latency_p90_s``,
read per layer where staleness aborts and barriers make it too unsteady
to hold to a bound."""
import statistics


def read(run):
    lat = sorted(run.latencies)
    if len(lat) < 2:
        return None
    return statistics.quantiles(lat, n=10, method="inclusive")[-1]
