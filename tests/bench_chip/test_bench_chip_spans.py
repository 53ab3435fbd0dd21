"""The program-span reduction (``benchmarks/chip/program_trace.py``) and the
metrics that read it, on a small hand-built trace whose values follow from
its events by hand.

Window 1 s. The engine's thread (line 1) polls an empty window, idles,
then collects batch 1 (two records, polls ending at 80 and 100 ms),
processes it (prep 4 ms, a stack dispatched 115-117 ms) and collects
batch 2 (one record at 150 ms), processes it (prep 3 ms, a frame
dispatched 186-190 ms, then blocked in ``app.wait`` for 100 ms). On the
chip: a frame run and a stack run queued before the trace (0-20 and
118-124 ms), batch 1's stack run (125-250 ms) and batch 2's frame run,
queued behind it (250-320 ms); ops run 0-20, 30-40, 130-240 and 260-310
ms."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import program_trace as pt  # noqa: E402
from benchmarks.chip import trace  # noqa: E402
from benchmarks.chip.harness import load_module  # noqa: E402
from benchmarks.chip.trace import NO_SPAN, Span, TraceSummary  # noqa: E402

NEW = ["engine_hold_mean_ms", "engine_blocked_share", "host_prep_p50_ms",
       "device_queue_mean_ms", "device_idle_held_share"]


@pytest.fixture(autouse=True)
def _harness_reduction(monkeypatch):
    """Loading a reader puts ``program_trace.summarize`` in the place of
    ``trace.summarize``; each test here gives the harness's back."""
    monkeypatch.setattr(trace, "summarize", trace.summarize)


def ms(*v):
    return tuple(x / 1e3 for x in v)


def sp(name, start, end, line=1, **stats):
    s, e = ms(start, end)
    return pt.ProgramSpan(name, s, e, stats.get("batch"), line=line, stats=stats)


def hand_built():
    spans = [
        sp("engine.collect", 0, 50, batch=1, records=0),
        sp("consumer.poll", 0, 50, records=0),
        sp("engine.idle", 50, 60),
        sp("engine.collect", 60, 110, batch=1, records=2),
        sp("consumer.poll", 60, 80, records=1),
        sp("consumer.poll", 80, 100, records=1),
        sp("consumer.poll", 100, 110, records=0),
        sp("engine.process", 110, 130, batch=1, records=2),
        sp("app.prep", 111, 115, frames=2),
        sp("app.dispatch", 115, 117, program="p_stack"),
        sp("engine.commit", 130, 131, batch=1),
        sp("engine.collect", 131, 181, batch=2, records=1),
        sp("consumer.poll", 131, 150, records=1),
        sp("consumer.poll", 150, 181, records=0),
        sp("engine.process", 181, 300, batch=2, records=1),
        sp("app.prep", 182, 185, frames=1),
        sp("app.dispatch", 186, 190, program="p_frame"),
        sp("app.wait", 190, 290),
        sp("engine.commit", 300, 301, batch=2),
        sp("app.wait", 400, 450, line=2),  # another thread's sync
    ]
    runs = [pt.ProgramRun("p_frame", *ms(0, 20), run_id=6),
            pt.ProgramRun("p_stack", *ms(118, 124), run_id=7),
            pt.ProgramRun("p_stack", *ms(125, 250), run_id=8, enqueued=ms(116)[0]),
            pt.ProgramRun("p_frame", *ms(250, 320), run_id=9, enqueued=ms(187)[0])]
    ops = [("backproject_pallas.2", *ms(0, 20)), ("fusion.1", *ms(30, 40)),
           ("backproject_pallas.1", *ms(130, 240)), ("backproject_pallas.2", *ms(260, 310))]
    bench = [Span("bench.process", *ms(110.5, 129.5), 0),
             Span("bench.process", *ms(181.5, 299.5), 1)]
    summary = TraceSummary(1.0, [ops], bench)
    summary.busy_s = 0.02 + 0.01 + 0.11 + 0.05
    summary.gaps = trace.idle_gaps(ops, bench, 1.0)
    summary.program = pt.ProgramTrace(1.0, spans, runs, ops, bench, summary.gaps)
    summary.gaps = summary.program.label_gaps(summary.gaps)
    return summary


class Run:
    def __init__(self, summary):
        self.trace = summary


def read(metric, summary):
    return load_module(ROOT / "benchmarks/chip/metrics" / f"{metric}.py", "metric").read(Run(summary))


def test_runs_go_to_the_dispatch_that_queued_them():
    prog = hand_built().program
    assert prog.engine_line() == 1
    got = [(r.run_id, r.batch, r.bench_batch) for r in prog.runs]
    # runs queued before the trace stay unattributed, also one that ran
    # after the first traced dispatch of its program
    assert got == [(6, None, None), (7, None, None), (8, 1, 0), (9, 2, 1)]
    assert prog.kernel_events(r"backproject_pallas") == [
        (pytest.approx(0.02), None), (pytest.approx(0.11), 0), (pytest.approx(0.05), 1)]


def test_gaps_read_the_engine_thread():
    gaps = [(round(s * 1e3, 6), round(e * 1e3, 6), label) for s, e, label in hand_built().gaps]
    assert gaps == [(20, 30, pt.EMPTY), (40, 130, pt.HOLDING), (240, 260, "app.wait"),
                    (310, 1000, NO_SPAN)]


@pytest.mark.parametrize("metric,value", [
    ("engine_hold_mean_ms", 71 / 3),  # records held 30, 10 and 31 ms
    ("engine_blocked_share", 10.0),  # 100 ms of app.wait on the engine's line
    ("host_prep_p50_ms", 3.5),  # preps of 4 and 3 ms
    ("device_queue_mean_ms", 36.0),  # queued at 116 and 187 ms, run at 125 and 250
    ("device_idle_held_share", 3.7),  # batch 1 held 80-117 ms, the chip idle
])
def test_each_reader_on_the_hand_built_trace(metric, value):
    assert read(metric, hand_built()) == pytest.approx(value)


def test_held_idle_is_a_part_of_idle():
    summary = hand_built()
    idle = read("device_idle_share", summary)
    assert idle == pytest.approx(100 * (1 - 0.19))
    assert read("device_idle_held_share", summary) <= idle


@pytest.mark.parametrize("metric", NEW)
def test_readers_stay_silent_without_program_spans(metric):
    """The parent program has no spans: the reduction finds nothing, and
    a summary the reduction never saw has no ``program``."""
    summary = hand_built()
    summary.program = pt.ProgramTrace(1.0, [], summary.program.runs, summary.ops[0],
                                      summary.spans, summary.gaps)
    assert read(metric, summary) is None
    assert read(metric, None) is None


@pytest.mark.parametrize("metric", NEW)
def test_a_window_the_reduction_never_saw_is_an_error(metric):
    """Had the harness reduced the trace without this module, the readers
    would find nothing and the metrics would vanish from the line."""
    with pytest.raises(RuntimeError, match="program_trace"):
        read(metric, TraceSummary(1.0, [], []))


def test_a_trace_without_program_spans_keeps_the_harness_reduction():
    """``small.xplane.pb`` was recorded before the program had spans: the
    extended reduction reads the same busy time, ops and gap labels, and
    its readers find nothing."""
    small = ROOT / "benchmarks/chip/fixtures/small.xplane.pb"
    base = pt._summarize(small, 0.2)
    summary = pt.summarize(small, 0.2)
    assert summary.gaps == base.gaps and summary.busy_s == base.busy_s
    assert summary.ops == base.ops and summary.spans == base.spans
    assert summary.program.engine_line() is None
    assert all(r.dispatch is None for r in summary.program.runs)
    assert len(summary.program.runs) == 7
    for metric in NEW:
        assert read(metric, summary) is None


def test_install_puts_the_extended_reduction_in_place():
    pt.install()
    assert trace.summarize is pt.summarize
    assert pt._summarize is not pt.summarize


def test_a_failed_reduction_fails_the_traced_run(monkeypatch):
    def broken(path, summary):
        raise ValueError("unreadable program spans")

    monkeypatch.setattr(pt, "read", broken)
    with pytest.raises(ValueError, match="unreadable"):
        pt.summarize(ROOT / "benchmarks/chip/fixtures/small.xplane.pb", 0.2)


# -- the fixture recorded on one v5e -------------------------------------------
# benchmarks/chip/fixtures/record_spans.py: a gridrec stage at n = 128 takes
# bursts of frames faster than the chip reconstructs them; the profiler
# starts with batches in flight. Its truth file lists every program call.

FIXTURE = ROOT / "benchmarks/chip/fixtures/small_spans.xplane.pb"
TRUTH = json.loads((FIXTURE.parent / "small_spans.truth.json").read_text())


@pytest.fixture(scope="module")
def recorded():
    return pt.summarize(FIXTURE, TRUTH["window_s"])


def _modules():
    """run_id -> the full module name of each program run, read directly."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(str(FIXTURE)).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == pt.MODULES_LINE:
                    out.update({dict(e.stats)["run_id"]: e.name for e in line.events})
    return out


@pytest.mark.parametrize("program", ["gridrec_frame", "gridrec_stack"])
def test_attribution_matches_the_recorded_truth(recorded, program):
    calls = [c for c in TRUTH["calls"] if c["program"] == program]
    traced = [c for c in calls if c["traced"]]
    assert all(c["traced"] is not None for c in calls)
    runs = sorted((r for r in recorded.program.runs if r.program == program),
                  key=lambda r: r.start)
    attributed = [r for r in runs if r.dispatch is not None]
    # every traced call has its run, in order; a batch whose engine.process
    # began before the trace has no batch on the trace's side
    assert len(attributed) == len(traced)
    for run, call in zip(attributed, traced):
        if run.batch is not None:
            assert (run.bench_batch, run.batch) == (call["batch"], call["engine_batch"])
    assert sum(r.batch is not None for r in attributed) >= len(traced) - 1
    # runs before the first attributed one came from calls made before the trace
    first = runs.index(attributed[0]) if attributed else len(runs)
    assert all(r.dispatch is None for r in runs[:first])
    assert first <= len(calls) - len(traced)
    # a program's fingerprint is one padded depth: each run's matches its call's
    modules = _modules()
    depth_of = {}
    for run, call in zip(attributed, traced):
        assert depth_of.setdefault(modules[run.run_id], call["depth"]) == call["depth"]
    if program == "gridrec_stack":
        assert len(set(depth_of.values())) == len(depth_of) >= 2


def test_runs_that_queued_are_attributed(recorded):
    """Runs that waited on the chip behind an earlier batch's (each starts
    as the run before it ends, and its dispatch began before that) are
    among those checked above. The trace's device and host clocks differ
    by about a millisecond, so a wait is read off the chip alone."""
    runs = sorted(recorded.program.runs, key=lambda r: r.start)
    queued = [r for prev, r in zip(runs, runs[1:])
              if r.dispatch is not None and r.start - prev.end < 20e-6
              and r.dispatch.start < prev.end and prev.dispatch is not r.dispatch]
    assert len(queued) >= 3


@pytest.mark.parametrize("metric,value", [
    # read off the trace's events by hand: 61 records held after their poll
    ("engine_hold_mean_ms", 2.396078),
    # 11 waits on the engine's line, 0.195 ms in all of a 171.556 ms window
    ("engine_blocked_share", 0.113678),
    # 11 batches, each one app.prep
    ("host_prep_p50_ms", 0.46174),
    # 11 batches; a run on an idle chip starts up to 1.30 ms before the
    # host's DoEnqueueProgram of it begins: the device plane's clock reads
    # that much early against the host's
    ("device_queue_mean_ms", -0.587103),
    # 11 batches held from their first record to their dispatch
    ("device_idle_held_share", 5.416205),
])
def test_each_reader_on_the_recorded_trace(recorded, metric, value):
    assert read(metric, recorded) == pytest.approx(value, abs=1e-5)
    assert read("device_idle_held_share", recorded) <= read("device_idle_share", recorded)
