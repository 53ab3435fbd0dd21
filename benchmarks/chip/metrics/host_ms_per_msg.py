"""host_ms_per_msg: the engine's own processing time (BatchMetrics
processing_delay: host prep plus dispatch, since the processor returns
before the device finishes) summed over the window's batches, per message."""


def read(run):
    n = sum(r for r, _ in run.engine)
    if n == 0:
        return None
    return sum(dt for _, dt in run.engine) / n * 1e3
