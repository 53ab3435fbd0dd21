"""service_p50_ms: per message, the start of its batch's process_fn call to
its result ready on the device (host prep, dispatch, device queue and
compute); the median over the window's processed messages."""
import numpy as np

from benchmarks.chip.stats import percentile


def read(run):
    start = np.full(len(run.due), np.nan)
    for b in run.batches:
        start[b.messages] = b.start
    return percentile((run.done - start) * 1e3, 50)
