"""engine_blocked_share: percent of the traced window the engine's thread
spends in ``app.wait``, blocked on the device because the processor's
window of in-flight batches is full (program spans)."""
from benchmarks.chip import program_trace

program_trace.install()


def read(run):
    prog = program_trace.of(run)
    if prog is None or prog.engine_line() is None:
        return None
    return prog.share((s.start, s.end) for s in prog.on_line(prog.engine_line(), "app.wait"))
