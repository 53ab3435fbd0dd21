"""result_latency_p95_ms: the 95th percentile of the per-message latency of
``result_latency_p50_ms``, over all messages due in the window."""
from benchmarks.chip.stats import percentile


def read(run):
    return percentile(run.latency_s * 1e3, 95)
