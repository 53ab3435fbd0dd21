"""result_latency_p50_ms: per message, its due time to its result ready on
the device (the watcher's stamp); the median over every message due in the
window that has a result (one without is failed, not fast)."""
from benchmarks.chip.stats import percentile


def read(run):
    return percentile(run.latency_s * 1e3, 50)
