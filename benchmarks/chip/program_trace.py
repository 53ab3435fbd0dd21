"""The system's own spans in a profiler trace, and each device program run
attributed to the batch that dispatched it.

The program marks its layers with host spans on the profiler's clock
(``repro.elastic.metrics.span``): ``consumer.poll`` (stat ``records``), the
micro-batch engine's ``engine.collect``, ``engine.process``,
``engine.commit`` (each with the engine's batch id as the stat ``batch``)
and ``engine.idle``, and inside ``engine.process`` the processor's
``app.prep``, ``app.dispatch`` (stat ``program``: the jitted program's
name) and ``app.wait`` (blocked on the device). A thread's spans share one
host line.

The device plane's ``XLA Modules`` line has one event per program run,
named ``jit_<program>(<fingerprint>)``, with the stat ``run_id``; the host
event ``DoEnqueueProgram`` with the same ``run_id`` is the runtime queueing
that run. One chip runs its programs in the order they were queued, so the
runs of one program follow its dispatches in order: each ``app.dispatch``
takes the first run of its program not yet taken that was queued after
the dispatch began. A run queued before the first traced dispatch of its
program belongs to a dispatch made before the trace began, and stays
unattributed.

``summarize`` is ``trace.summarize`` plus this reduction, as
``summary.program``, with each idle gap relabelled by the innermost span the
engine's thread was in at its midpoint (``engine.collect`` reads
``engine.collect:holding`` once a poll of that window has returned records,
else ``engine.collect:empty``); a gap no such span covers keeps its label.
The harness calls ``trace.summarize``: ``install()``, which the metrics that
read this reduction call when they load, puts ``summarize`` in its place for
the whole process. A trace this reduction cannot read fails the traced run;
a trace without program spans (a program that has none) reduces to no
batches and no attributed runs, and its readers find nothing.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from pathlib import Path

from benchmarks.chip import trace
from benchmarks.chip.stats import union_length
from benchmarks.chip.trace import DEVICE_PLANE, HOST_PLANE, Span, TraceSummary

PREFIXES = ("consumer.", "engine.", "app.")
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
HOLDING = "engine.collect:holding"
EMPTY = "engine.collect:empty"

#: the harness's own reduction, which ``summarize`` extends
_summarize = trace.summarize


def program_name(module: str) -> str:
    """``jit_gridrec_frame(123)`` -> ``gridrec_frame``."""
    head = module.split("(", 1)[0]
    return head[4:] if head.startswith("jit_") else head


@dataclass
class ProgramSpan(Span):
    #: index of its host line (one line a thread)
    line: int = -1
    stats: dict = field(default_factory=dict)

    def covers(self, other: "Span") -> bool:
        return self.start <= other.start and other.end <= self.end


@dataclass
class ProgramRun:
    """One run of a device program (an ``XLA Modules`` event)."""

    program: str
    start: float
    end: float
    run_id: int | None = None
    #: start of the host's ``DoEnqueueProgram`` for this run, if traced
    enqueued: float | None = None
    dispatch: ProgramSpan | None = None
    #: the engine's batch id and the harness's batch index of ``dispatch``
    batch: int | None = None
    bench_batch: int | None = None


@dataclass
class BatchSpans:
    """One engine batch as its engine thread's spans show it."""

    batch: int
    process: ProgramSpan
    #: the polls of its window that returned records, if the window is traced
    polls: list[ProgramSpan]
    prep: list[ProgramSpan]
    dispatch: list[ProgramSpan]
    runs: list[ProgramRun]


@dataclass
class ProgramTrace:
    """Seconds on the trace's clock, as ``TraceSummary``."""

    window_s: float
    spans: list[ProgramSpan]
    runs: list[ProgramRun]
    #: the first chip's XLA ops, (name, start, end)
    ops: list[tuple[str, float, float]] = field(default_factory=list)
    #: the harness's ``bench.process`` spans
    bench: list[Span] = field(default_factory=list)
    #: the first chip's idle stretches of the window, (start, end, label)
    gaps: list[tuple[float, float, str]] = field(default_factory=list)

    def __post_init__(self):
        self.spans.sort(key=lambda s: s.start)
        attribute(self.runs, self.spans, self.bench)

    def engine_line(self) -> int | None:
        """The host line with the most ``engine.process`` spans."""
        count: dict[int, int] = {}
        for s in self.spans:
            if s.name == "engine.process":
                count[s.line] = count.get(s.line, 0) + 1
        return max(count, key=count.get) if count else None

    def on_line(self, line: int | None, name: str | None = None) -> list[ProgramSpan]:
        return [s for s in self.spans
                if s.line == line and (name is None or s.name == name)]

    def batches(self) -> list[BatchSpans]:
        """Every batch whose ``engine.process`` lies in the trace."""
        line = self.engine_line()
        mine = self.on_line(line)
        runs: dict[int, list[ProgramRun]] = {}
        for r in self.runs:
            if r.dispatch is not None and r.dispatch.line == line and r.batch is not None:
                runs.setdefault(r.batch, []).append(r)
        out = []
        for p in (s for s in mine if s.name == "engine.process"):
            b = p.stats.get("batch")
            collect = next((s for s in reversed(mine) if s.name == "engine.collect"
                            and s.stats.get("batch") == b and s.end <= p.start
                            and s.stats.get("records", 0) > 0), None)
            polls = [s for s in mine if s.name == "consumer.poll" and collect is not None
                     and collect.covers(s) and s.stats.get("records", 0) > 0]
            inside = [s for s in mine if p.covers(s)]
            out.append(BatchSpans(
                b, p, polls,
                [s for s in inside if s.name == "app.prep"],
                [s for s in inside if s.name == "app.dispatch"],
                sorted(runs.get(b, []), key=lambda r: r.start)))
        return out

    def share(self, intervals) -> float:
        """Percent of the window that ``intervals`` cover."""
        inside = [(max(s, 0.0), min(e, self.window_s)) for s, e in intervals]
        return 100.0 * union_length([iv for iv in inside if iv[1] > iv[0]]) / self.window_s

    def idle_within(self, intervals) -> list[tuple[float, float]]:
        """The parts of ``intervals`` in which the chip is idle."""
        return [(max(s, gs), min(e, ge)) for s, e in intervals
                for gs, ge, _ in self.gaps if gs < e and s < ge]

    def kernel_events(self, pattern: str) -> list[tuple[float, int | None]]:
        """(device seconds, harness batch index) of each first-chip op whose
        name matches ``pattern``; the batch is that of the program run the
        op ran in, None where that run is not attributed."""
        rx = re.compile(pattern)
        runs = sorted(self.runs, key=lambda r: r.start)
        out, j = [], 0
        for name, s, e in sorted(self.ops, key=lambda o: o[1]):
            while j < len(runs) and runs[j].end < s:
                j += 1
            if rx.search(name):
                run = runs[j] if j < len(runs) and runs[j].start <= s else None
                out.append((e - s, None if run is None else run.bench_batch))
        return out

    def label_gaps(self, gaps: list[tuple[float, float, str]]) -> list[tuple[float, float, str]]:
        """Each gap labelled by the innermost engine-thread span at its
        midpoint (see the module's docstring), else as it was."""
        mine = self.on_line(self.engine_line())
        starts = [s.start for s in mine]
        longest = max((s.end - s.start for s in mine), default=0.0)
        #: per engine.collect, the end of its first poll that returned records
        held_from = {id(c): min((s.end for s in mine if s.name == "consumer.poll"
                                 and c.covers(s) and s.stats.get("records", 0) > 0),
                                default=None)
                     for c in mine if c.name == "engine.collect"}
        out = []
        for s, e, label in gaps:
            mid = (s + e) / 2
            i = bisect.bisect_right(starts, mid)
            covering = []
            while i > 0 and mine[i - 1].start >= mid - longest:
                i -= 1
                if mid < mine[i].end:
                    covering.append(mine[i])
            if covering:
                inner = max(covering, key=lambda sp: sp.start)
                collect = next((sp for sp in covering if sp.name == "engine.collect"), None)
                if collect is not None and inner.name in ("engine.collect", "consumer.poll"):
                    since = held_from[id(collect)]
                    label = HOLDING if since is not None and since <= mid else EMPTY
                else:
                    label = inner.name
            out.append((s, e, label))
        return out


def attribute(runs: list[ProgramRun], spans: list[ProgramSpan], bench: list[Span]) -> None:
    """Give each run the ``app.dispatch`` that queued it (see the module's
    docstring), and through it the engine's and the harness's batch."""
    for r in runs:
        r.dispatch = r.batch = r.bench_batch = None
    by_program: dict[str, list[ProgramRun]] = {}
    for r in sorted(runs, key=lambda r: (r.start if r.run_id is None else r.run_id)):
        by_program.setdefault(r.program, []).append(r)
    dispatches: dict[str, list[ProgramSpan]] = {}
    for s in spans:
        if s.name == "app.dispatch" and "program" in s.stats:
            dispatches.setdefault(str(s.stats["program"]), []).append(s)
    processes = [s for s in spans if s.name == "engine.process"]
    for program, ds in dispatches.items():
        todo = iter(by_program.get(program, []))
        for d in sorted(ds, key=lambda s: s.start):
            run = next((r for r in todo if r.enqueued is not None and r.enqueued >= d.start), None)
            if run is None:
                break
            run.dispatch = d
            p = next((p for p in processes if p.line == d.line and p.covers(d)), None)
            run.batch = None if p is None else p.stats.get("batch")
            b = next((b for b in bench if b.name == "bench.process"
                      and b.start <= d.start < b.end), None)
            run.bench_batch = None if b is None else b.batch


def read(path: str | Path, summary: TraceSummary) -> ProgramTrace:
    """The program's spans and the first chip's program runs in one trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(trace.find_xplane(path)))
    spans: list[ProgramSpan] = []
    runs: list[ProgramRun] = []
    enqueued: dict[int, float] = {}
    chip_seen = False
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name) and not chip_seen:
            chip_seen = True
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for e in line.events:
                        s = e.start_ns * 1e-9
                        rid = dict(e.stats).get("run_id")
                        runs.append(ProgramRun(program_name(e.name), s,
                                               s + e.duration_ns * 1e-9,
                                               None if rid is None else int(rid)))
        elif plane.name == HOST_PLANE:
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    s = e.start_ns * 1e-9
                    if e.name.startswith(PREFIXES):
                        stats = dict(e.stats)
                        b = stats.get("batch")
                        spans.append(ProgramSpan(e.name, s, s + e.duration_ns * 1e-9,
                                                 None if b is None else int(b),
                                                 line=i, stats=stats))
                    elif e.name == ENQUEUE:
                        rid = dict(e.stats).get("run_id")
                        if rid is not None:
                            enqueued[int(rid)] = min(s, enqueued.get(int(rid), s))
    for r in runs:
        r.enqueued = enqueued.get(r.run_id)
    return ProgramTrace(summary.window_s, spans, runs,
                        summary.ops[0] if summary.ops else [],
                        [s for s in summary.spans if s.name == "bench.process"],
                        list(summary.gaps))


def summarize(path: str | Path, window_s: float) -> TraceSummary:
    """``trace.summarize``, plus ``summary.program`` and the gaps
    relabelled."""
    summary = _summarize(path, window_s)
    summary.program = read(path, summary)
    summary.gaps = summary.program.label_gaps(summary.gaps)
    return summary


def install() -> None:
    """Make the harness's ``trace.summarize`` this module's ``summarize``."""
    trace.summarize = summarize


def of(run) -> ProgramTrace | None:
    """The program reduction of a run's traced window; None for an
    untraced run. A traced run the reduction never saw is an error: the
    harness did not call ``summarize`` in place of ``trace.summarize``."""
    if run.trace is None:
        return None
    if not hasattr(run.trace, "program"):
        raise RuntimeError("the traced window was not reduced by program_trace.summarize")
    return run.trace.program
