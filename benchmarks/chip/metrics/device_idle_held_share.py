"""device_idle_held_share: percent of the traced window in which no XLA op
runs on the chip while a batch's records sit on the host: from the end
of the first poll of its window that returned records to the end of its
first ``app.dispatch``. A part of ``device_idle_share``: the idle gaps of
the window that these stretches cross (program spans and the device
trace)."""
from benchmarks.chip import program_trace

program_trace.install()


def read(run):
    prog = program_trace.of(run)
    if prog is None or not prog.ops:
        return None
    held = [(b.polls[0].end, b.dispatch[0].end) for b in prog.batches()
            if b.polls and b.dispatch]
    if not held:
        return None
    return prog.share(prog.idle_within(held))
