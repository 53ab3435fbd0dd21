"""host_prep_p50_ms: per batch, the time its processor spends in
``app.prep``: decoding, stacking and padding the frames and handing them
to the device. The median over the batches whose spans lie in the traced
window (program spans)."""
from benchmarks.chip import program_trace
from benchmarks.chip.stats import percentile

program_trace.install()


def read(run):
    prog = program_trace.of(run)
    if prog is None:
        return None
    return percentile([sum(s.end - s.start for s in b.prep) * 1e3
                       for b in prog.batches() if b.prep], 50)
