"""backlog_growth_msgs_s: least-squares slope of the stream's committed-offset
backlog (``stream.lag()``, sampled every 0.1 s) over the window. About 0
where the offered rate is sustained. A broker whose shared-memory ring is
full holds the producer back, so that backlog then waits in the generator
instead: the run's earlier line gives the slope of due minus done too."""
from benchmarks.chip.stats import slope


def read(run):
    if len(run.lag) < 2:
        return None
    ts, lags = zip(*run.lag)
    return slope(ts, lags)
