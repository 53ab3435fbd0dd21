"""Profiler trace (``.xplane.pb``) -> device busy time, per-op device time,
kernel events attributed to batches, and idle gaps labelled by the host.

On a TPU the trace holds one plane per chip (``/device:TPU:<i>``) whose
``XLA Ops`` line has one event per HLO op that ran, named by its HLO text
(``%backproject_pallas.1 = f32[...] custom-call(...)``), and a host plane
(``/host:CPU``) whose lines hold, among others, the benchmark's own spans
(``jax.profiler.TraceAnnotation``): ``bench.process`` around each call of
the engine's process function, with the batch's index as the stat
``batch``; ``bench.send`` around each generator send; ``bench.watch``
around the watcher's wait for a batch's result. Every event's start is in
nanoseconds from the trace's start, on one clock for all planes.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

from benchmarks.chip.stats import union_length

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
#: which host span names an idle gap, highest first
SPAN_PRIORITY = ("bench.process", "bench.send", "bench.watch")
NO_SPAN = "no bench span (engine poll, broker, or nothing to do)"


def op_name(event_name: str) -> str:
    """``%name.3 = f32[..] op(..)`` -> ``name.3``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


@dataclass
class Span:
    name: str
    start: float
    end: float
    batch: int | None = None


@dataclass
class TraceSummary:
    """Seconds throughout, on the trace's clock (0 = trace start)."""

    window_s: float
    #: per chip: (op name, start, end) of every XLA op
    ops: list[list[tuple[str, float, float]]]
    spans: list[Span]
    busy_s: float = 0.0
    gaps: list[tuple[float, float, str]] = field(default_factory=list)

    def op_seconds(self) -> dict[str, float]:
        total: dict[str, float] = {}
        for chip in self.ops:
            for name, s, e in chip:
                total[name] = total.get(name, 0.0) + (e - s)
        return total

    def kernel_events(self, pattern: str) -> list[tuple[float, int | None]]:
        """(device seconds, batch index) of each op whose name matches
        ``pattern``; the batch is that of the latest ``bench.process`` span
        that began before the op (one chip executes in order)."""
        rx = re.compile(pattern)
        starts = sorted((s.start, s.batch) for s in self.spans
                        if s.name == "bench.process" and s.batch is not None)
        out = []
        for chip in self.ops:
            j, cur = 0, None
            for name, s, e in sorted(chip, key=lambda o: o[1]):
                while j < len(starts) and starts[j][0] <= s:
                    cur = starts[j][1]
                    j += 1
                if rx.search(name):
                    out.append((e - s, cur))
        return out

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: -(g[1] - g[0]))[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[label, e - s] for s, e, label in gaps]}


def find_xplane(path: str | Path) -> Path:
    p = Path(path)
    if p.is_file():
        return p
    found = sorted(p.glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {p}")
    return found[-1]


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def summarize(path: str | Path, window_s: float) -> TraceSummary:
    """Read one trace; ``window_s`` is how long it was recording."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(find_xplane(path)))
    ops, spans = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            chip = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        s = e.start_ns * 1e-9
                        chip.append((op_name(e.name), s, s + e.duration_ns * 1e-9))
            ops.append(chip)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        s = e.start_ns * 1e-9
                        b = _stat(e, "batch")
                        spans.append(Span(e.name, s, s + e.duration_ns * 1e-9,
                                          None if b is None else int(b)))
    summary = TraceSummary(window_s, ops, spans)
    summary.busy_s = (sum(_busy(chip, window_s) for chip in ops) / len(ops)) if ops else 0.0
    if ops:
        summary.gaps = idle_gaps(ops[0], spans, window_s)
    return summary


def _clip(chip, window_s):
    return [(max(s, 0.0), min(e, window_s)) for _, s, e in chip if e > 0 and s < window_s]


def _busy(chip, window_s) -> float:
    return union_length(_clip(chip, window_s))


def idle_gaps(chip, spans: list[Span], window_s: float) -> list[tuple[float, float, str]]:
    """Stretches of [0, window_s) with no op on the chip, each labelled by
    the highest-priority bench span covering its midpoint."""
    merged: list[list[float]] = []
    for s, e in sorted(_clip(chip, window_s)):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    edges = [0.0] + [x for iv in merged for x in iv] + [window_s]
    gaps = []
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            mid = (s + e) / 2
            covering = {sp.name for sp in spans if sp.start <= mid < sp.end}
            label = next((n for n in SPAN_PRIORITY if n in covering), NO_SPAN)
            gaps.append((s, e, label))
    return gaps
