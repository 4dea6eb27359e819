"""95th percentile of the window's step times, in ms (host clock).  A
step is timed from its dispatch until its loss reaches the host."""
import statistics


def read(run):
    if run.cell.counts().get("tokens_per_step") is None:
        return None
    d = run.durations
    if len(d) < 2:
        return 1e3 * d[0]
    return 1e3 * statistics.quantiles(d, n=100, method="inclusive")[94]
