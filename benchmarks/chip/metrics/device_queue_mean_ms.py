"""device_queue_mean_ms: per batch, the runtime's enqueueing of the first
program run attributed to the batch (its ``DoEnqueueProgram``) to that
run's start on the chip (``program_trace``: runs follow their program's
dispatches in order): how long the batch's work waits behind earlier
batches' on the device, zero when the chip is free. The mean over the
batches of the traced window with an attributed run; a mean, as the wait
is zero for a batch that finds the chip free and up to a frame's time for
one that does not, and a median flips between the two (program spans and
the device trace)."""
from benchmarks.chip import program_trace

program_trace.install()


def read(run):
    prog = program_trace.of(run)
    if prog is None:
        return None
    waits = [(b.runs[0].start - b.runs[0].enqueued) * 1e3
             for b in prog.batches() if b.runs]
    return sum(waits) / len(waits) if waits else None
