"""queue_wait_p95_ms: per message, its due time to the start of the engine's
process_fn call for its batch (broker, batching window, engine queue);
95th percentile over the messages of the window that were processed."""
import numpy as np

from benchmarks.chip.stats import percentile


def read(run):
    start = np.full(len(run.due), np.nan)
    for b in run.batches:
        start[b.messages] = b.start
    return percentile((start - run.due) * 1e3, 95)
