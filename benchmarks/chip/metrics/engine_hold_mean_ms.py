"""engine_hold_mean_ms: per record, the end of the engine's ``consumer.poll``
that returned it to the start of its batch's ``engine.process``: what is
left of the batch window once the record is in hand, then the engine's
own steps before the call. The mean over the records of the batches whose
spans lie in the traced window; a mean, as the hold has two modes (a
window closed on its first record, or one run to its end) and a median
flips between them from run to run (program spans)."""
from benchmarks.chip import program_trace

program_trace.install()


def read(run):
    prog = program_trace.of(run)
    if prog is None:
        return None
    # (hold, records) of each poll that returned records
    polls = [(b.process.start - poll.end, int(poll.stats["records"]))
             for b in prog.batches() for poll in b.polls]
    records = sum(n for _, n in polls)
    return 1e3 * sum(h * n for h, n in polls) / records if records else None
