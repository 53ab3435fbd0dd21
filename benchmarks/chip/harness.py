"""One run of one cell: load its files, build the pipeline through the
normal path, warm up, offer the open-loop traffic for the window, drain,
check the results against the plain reference, and read the metrics.

Everything specific to a configuration, a traffic mix, a processor kind
or a metric is found by name under this directory:

* ``configs/<config>.json``: the deployment (broker, topic, stage, sizes,
  stated guarantees, correctness limits);
* ``traffic/<mix>.json``: the arrival process (see ``traffic.py``);
* ``kinds/<kind>.py``: payload pool, warm-up, output collector, plain
  reference, comparison and work counts for one processor kind, and
  optionally ``tap(app)``, which records what the stage's programs return
  besides its state;
* ``metrics/<metric>.py``: ``read(run) -> float | None`` for one metric.
"""
from __future__ import annotations

import importlib.util
import json
import queue
import shutil
import sys
import tempfile
import threading
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Any

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: how long past the window's close the run waits for due messages
DRAIN_S = 60.0
#: period of the backlog sampler
LAG_PERIOD_S = 0.1
#: the traced run records the middle this-many seconds of its window
TRACE_S = 10.0


class BenchError(Exception):
    """The cell cannot be run here: a file is missing or no chip is found."""


def load_json(path: Path) -> Any:
    if not path.is_file():
        raise BenchError(f"missing file: {path.relative_to(ROOT) if path.is_relative_to(ROOT) else path}")
    return json.loads(path.read_text())


def load_module(path: Path, prefix: str) -> ModuleType:
    """Import one plugin file by path (metric names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing file: {path.relative_to(ROOT)}")
    name = f"bench_chip_{prefix}_" + "".join(c if c.isalnum() else "_" for c in path.stem)
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    kind: ModuleType
    chips: int
    end_to_end: list[dict]
    per_layer: list[dict]
    readers: dict[str, ModuleType]


def _reports(metric: dict, cell: str, e2e_names: set[str] | None = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric.get("moves") in e2e_names


def load_cell(workload: str, bench_path: Path | None = None) -> Cell:
    """Resolve a cell and every file it needs; a missing one is refused."""
    bench = load_json(bench_path or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"workload {workload!r} names unknown config {w['config']!r}")
    config = load_json(ROOT / configs[w["config"]]["file"])
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    kind = load_module(HERE / "kinds" / f"{config['kind']}.py", "kind")
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, workload, names)]
    readers = {m["name"]: load_module(HERE / "metrics" / f"{m['name']}.py", "metric")
               for m in e2e + per_layer}
    return Cell(workload, config, traffic, kind, int(w["chips"]), e2e, per_layer, readers)


# -- what one run records -----------------------------------------------------


@dataclass
class Batch:
    index: int
    messages: list[int]  # message indices, in the order the engine gave them
    start: float  # host monotonic: the engine's process_fn call began
    end: float  # ... and returned
    done: float = float("nan")  # the watcher saw its result ready on the device
    drained: float = float("nan")  # the processor's own double buffer drained it


@dataclass
class RunRecord:
    """Everything a metric reader or a kind's comparison may read."""

    cell: str
    seed: int
    seconds: float
    config: dict
    setup_s: float
    t0: float  # window start, host monotonic
    due: np.ndarray  # per message, host monotonic
    sent: np.ndarray
    done: np.ndarray  # nan where no result became ready
    order: np.ndarray  # pool entry each message carried
    batches: list[Batch]
    lag: list[tuple[float, int]]  # (host monotonic, committed-offset backlog)
    engine: list[tuple[int, float]]  # (records, processing_delay) per window batch
    work: dict
    peaks: dict | None
    compiles_in_window: int
    trace: Any = None  # trace.TraceSummary of the window when --trace 1
    trace_t0: float = float("nan")
    trace_t1: float = float("nan")
    partition: np.ndarray | None = None
    extra: dict = field(default_factory=dict)

    @property
    def close(self) -> float:
        return self.t0 + self.seconds

    @property
    def latency_s(self) -> np.ndarray:
        """Due time to result ready, for every message that has a result."""
        lat = self.done - self.due
        return lat[np.isfinite(lat)]


# -- the timed path's wrapper and its completion watcher -----------------------


class Recorder:
    """Shared by the wrapped processor (engine thread) and the watcher."""

    def __init__(self, collector, annotate=None):
        self.arm(np.zeros(0))
        self.batches: list[Batch] = []
        self.collector = collector
        self.annotate = annotate
        self.error: BaseException | None = None
        self._q: queue.Queue = queue.Queue()
        self._by_result: dict[int, Batch] = {}
        self._lock = threading.Lock()
        self._thread = threading.Thread(target=self._watch, name="bench-watcher",
                                        daemon=True)
        self._thread.start()

    def arm(self, timestamps: np.ndarray) -> None:
        """Name the window's messages by their due timestamps (unique)."""
        self.index = {float(t): i for i, t in enumerate(timestamps)}
        self.done = np.full(len(timestamps), np.nan)
        self.seen = np.zeros(len(timestamps), dtype=np.int64)
        self.partition = np.full(len(timestamps), -1, dtype=np.int64)

    def span(self, name: str, **stats):
        return self.annotate(name, **stats) if self.annotate else nullcontext()

    def add_batch(self, messages: list[int], start: float, end: float, result,
                  outputs=None) -> None:
        with self._lock:
            b = Batch(len(self.batches), messages, start, end)
            self.batches.append(b)
            self._by_result[id(result)] = b
            self.seen[messages] += 1
        self._q.put((b, result, outputs))

    def processor_drained(self, result) -> None:
        with self._lock:
            b = self._by_result.pop(id(result), None)
        if b is not None:
            b.drained = time.monotonic()

    def _watch(self) -> None:
        import jax

        while True:
            item = self._q.get()
            if item is None:
                return
            b, result, outputs = item
            try:
                with self.span("bench.watch"):
                    jax.block_until_ready((result, outputs))
                b.done = time.monotonic()
                self.done[b.messages] = b.done
                self.collector.on_ready(b, result, outputs)
            except BaseException as e:  # surfaced after the drain
                self.error = e
            del result, outputs

    def close(self, timeout: float = 30.0) -> None:
        self._q.put(None)
        self._thread.join(timeout)


class TimedProcessor:
    """The registered processor, wrapped: records which messages make up
    each batch and hands each batch's returned array, and what ``take``
    (the kind's tap) says its programs returned, to the watcher."""

    def __init__(self, inner, recorder: Recorder, take=None):
        self.inner = inner
        self.recorder = recorder
        self.take = take
        hook = getattr(inner, "_on_complete", None)
        if callable(hook):
            def on_complete(result, meta, dt, _hook=hook):
                recorder.processor_drained(result)
                return _hook(result, meta, dt)
            inner._on_complete = on_complete

    def process(self, state, msgs):
        rec = self.recorder
        idx = [rec.index[m.timestamp] for m in msgs]
        rec.partition[idx] = [m.partition for m in msgs]
        start = time.monotonic()
        with rec.span("bench.process", batch=len(rec.batches)):
            out = self.inner.process(state, msgs)
        end = time.monotonic()
        rec.add_batch(idx, start, end, out, self.take() if self.take else None)
        return out

    def sync(self) -> None:
        self.inner.sync()

    def __getattr__(self, name):
        return getattr(self.inner, name)


# -- one run ---------------------------------------------------------------------


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def check_devices(cell: Cell, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise BenchError(f"no TPU: jax found {devices[0].platform!r}; this "
                             "benchmark measures only on the chip")
        if len(devices) < cell.chips:
            raise BenchError(f"cell {cell.name} needs {cell.chips} chips, "
                             f"jax found {len(devices)}")
    return devices


def load_peaks(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def _build_pipeline(cell: Cell, seed: int, recorder: Recorder):
    from repro.pipeline import Pipeline, register_processor
    from repro.pipeline.registry import make_processor

    cfg = cell.config
    stage = dict(cfg["stage"])
    options = dict(stage.pop("options", {}))
    options.update(cell.kind.seeded_options(cfg, seed))
    inner_name = stage.pop("processor")
    wrapped = f"bench.{cfg['name']}"
    made: list = []

    def factory(metrics=None, **opts):
        # the runner hands its metrics bus to factories that take one
        inner = make_processor(inner_name, opts, metrics=metrics)
        tap = getattr(cell.kind, "tap", None)
        proc = TimedProcessor(inner, recorder, tap(inner) if tap else None)
        made.append(proc)
        return proc

    register_processor(wrapped, factory)
    broker = cfg["broker"]
    topic = cfg["topic"]
    spec = (Pipeline.named(f"bench-{cfg['name']}")
            .broker(nodes=broker.get("nodes", 1), transport=broker.get("transport", "log"),
                    transport_options=broker.get("transport_options"))
            .topic(topic["name"], partitions=topic.get("partitions", 1))
            .stage("stage", topic=topic["name"], processor=wrapped, **stage, **options)
            .build())
    run = spec.run().start()
    return run, made[0]


def _sample_lag(stream, stop: threading.Event, out: list) -> None:
    while not stop.wait(LAG_PERIOD_S):
        out.append((time.monotonic(), int(sum(stream.lag().values()))))


@dataclass
class Outcome:
    record: RunRecord
    checks: dict  # name -> {"value", "limit", "pass_if"}
    memory_peak_bytes: int
    devices: list
    control_checks: dict | None = None

    @property
    def correct(self) -> bool:
        return all(passes(c) for c in self.checks.values())


def passes(check: dict) -> bool:
    v, lim = check["value"], check["limit"]
    return v >= lim if check.get("pass_if") == ">=" else v <= lim


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *, t_process: float,
             require_tpu: bool = True, compile_cache: bool = True, fault=None,
             control: bool = False, check: bool = True) -> Outcome:
    """One run of ``cell``. ``t_process`` is the host-monotonic time the
    process started (set-up is measured from it). ``fault`` (tests only)
    breaks the inner processor after warm-up, underneath the harness;
    ``control`` also reads the kind's lower-precision control.
    ``require_tpu`` and ``compile_cache`` are off only in the CPU tests,
    which must not point the process's compile cache anywhere."""
    import jax
    from jax.profiler import TraceAnnotation

    from benchmarks.chip import traffic as traffic_mod
    from benchmarks.chip.trace import summarize
    from repro.broker.producer import Producer
    from repro.utils import enable_compile_cache

    phases = {"imports_s": time.monotonic() - t_process}
    tick = time.monotonic()
    devices = check_devices(cell, require_tpu)
    used = devices[: cell.chips]
    peaks = load_peaks(devices[0].device_kind) if devices[0].platform == "tpu" else None
    if compile_cache:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    events = _JaxEvents()
    phases["devices_s"] = time.monotonic() - tick
    tick = time.monotonic()
    kind, cfg = cell.kind, cell.config
    pool = kind.make_pool(cfg, seed)
    phases["pool_s"] = time.monotonic() - tick
    tick = time.monotonic()
    due_off = _strictly_increasing(traffic_mod.due_offsets(cell.traffic, seconds))
    order = traffic_mod.payload_order(len(due_off), len(pool), seed)
    annotate = TraceAnnotation if trace else None
    collector = kind.Collector(cfg, seed)
    recorder = Recorder(collector, annotate)
    run, proc = _build_pipeline(cell, seed, recorder)
    stream = run.stream("stage")
    phases["pipeline_s"] = time.monotonic() - tick
    tick = time.monotonic()
    gen, lag_thread, trace_dir, summary = None, None, None, None
    stop_lag = threading.Event()
    lag: list = []
    try:
        kind.warmup(proc.inner, pool, cfg)
        if proc.take:
            proc.take()  # the warm-up's outputs are no window's
        if hasattr(proc.inner, "reset_stats"):
            proc.inner.reset_stats()
        phases["warmup_s"] = time.monotonic() - tick
        phases["cache_events"] = dict(events.cache)
        if fault is not None:
            fault(proc.inner)
        n_hist = len(stream.stats.history)
        compiles_before = events.compiles + _program_compiles(proc.inner)
        wall_minus_mono = time.time() - time.monotonic()
        t0 = time.monotonic() + 0.05
        due = t0 + due_off
        stamps = due + wall_minus_mono
        if len(np.unique(stamps)) != len(stamps):
            raise RuntimeError("two messages share a due timestamp")
        recorder.arm(stamps)
        producer = Producer(run.cluster, cfg["topic"]["name"])
        gen = traffic_mod.OpenLoopGenerator(producer, pool, order, due,
                                            wall_minus_mono=wall_minus_mono,
                                            annotate=annotate)
        setup_s = t0 - t_process
        gen.start()
        lag_thread = threading.Thread(target=_sample_lag, args=(stream, stop_lag, lag),
                                      name="bench-lag", daemon=True)
        lag_thread.start()
        trace_t0 = trace_t1 = float("nan")
        if trace:
            traced = min(TRACE_S, seconds)
            _sleep_until(t0 + (seconds - traced) / 2)
            trace_dir = tempfile.mkdtemp(prefix="bench-chip-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            trace_t0 = time.monotonic()  # the trace's clock starts inside this call
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            _sleep_until(trace_t0 + traced)
            trace_t1 = time.monotonic()
            jax.profiler.stop_trace()
        _sleep_until(t0 + seconds)
        stop_lag.set()
        deadline = t0 + seconds + DRAIN_S
        while time.monotonic() < deadline:
            if gen.error or recorder.error or getattr(stream, "_error", None):
                break
            if gen.finished() and np.isfinite(recorder.done).sum() >= len(due):
                break
            time.sleep(0.02)
        compiles_in_window = (events.compiles + _program_compiles(proc.inner)
                              - compiles_before)
        engine = [(h.n_records, h.processing_delay)
                  for h in stream.stats.history[n_hist:]]
        mem_peak = memory_peak_bytes(used)
        # a send still blocked when the drain gives up fails at teardown:
        # its message is already counted as failed, so only earlier
        # errors are the run's
        errors = [gen.error, recorder.error]
        if trace:
            summary = summarize(trace_dir, window_s=trace_t1 - trace_t0)
    finally:
        if gen is not None:
            gen.stop()
        stop_lag.set()
        if lag_thread is not None:
            lag_thread.join(5)
        run.stop()
        recorder.close()
        events.close()
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    for err in (*errors, *run.errors):
        if err is not None:
            raise err

    record = RunRecord(
        cell=cell.name, seed=seed, seconds=seconds, config=cfg, setup_s=setup_s, t0=t0,
        due=due, sent=gen.sent, done=recorder.done.copy(), order=order,
        batches=list(recorder.batches), lag=lag, engine=engine,
        work=kind.work(cfg), peaks=peaks, compiles_in_window=compiles_in_window,
        trace=summary, trace_t0=trace_t0, trace_t1=trace_t1,
        partition=recorder.partition.copy())
    # the program's state goes before the reference runs on the chip
    del run, proc, stream
    kept = collector.finish()
    t_ref = time.monotonic()
    checks = delivery_checks(record, recorder.seen)
    if check:
        checks.update(kind.compare(record, pool, kept, cfg, seed))
    record.extra["reference_s"] = time.monotonic() - t_ref
    record.extra["setup_phases"] = phases
    out = Outcome(record, checks, mem_peak, used)
    if control:
        out.control_checks = kind.control(record, pool, kept, cfg, seed)
    return out


def _strictly_increasing(t: np.ndarray) -> np.ndarray:
    """Due times at least a microsecond apart, so that each message's
    wall-clock timestamp (float64 resolves 0.25 us there) names it."""
    t = np.array(t, dtype=np.float64)
    for i in range(1, len(t)):
        if t[i] < t[i - 1] + 1e-6:
            t[i] = t[i - 1] + 1e-6
    return t


def _sleep_until(t: float) -> None:
    while (d := t - time.monotonic()) > 0:
        time.sleep(min(d, 0.5))


def _program_compiles(proc) -> int:
    n = getattr(proc, "compiles", 0)
    return int(n) if isinstance(n, int) and n >= 0 else 0


class _JaxEvents:
    """Counts, while open, XLA backend compiles and persistent-cache events
    anywhere in the process."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon

        self._mon = mon
        self.compiles = 0
        self.cache: dict[str, int] = {}
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == self.COMPILE:
            self.compiles += 1

    def _on_event(self, event: str, **kw) -> None:
        if "compilation_cache" in event:
            self.cache[event] = self.cache.get(event, 0) + 1

    def close(self) -> None:
        self._mon.unregister_event_duration_listener(self._on_duration)
        self._mon.unregister_event_listener(self._on_event)


def delivery_checks(record: RunRecord, seen: np.ndarray) -> dict:
    """Exactly once, in partition order, done by the end of the drain: a
    message dropped, duplicated, reordered or without a result is failed."""
    failed = (seen != 1) | ~np.isfinite(record.done)
    last: dict[int, int] = {}
    for b in record.batches:
        for i in b.messages:
            p = int(record.partition[i])
            if i <= last.get(p, -1):
                failed[i] = True
            last[p] = max(last.get(p, -1), i)
    return {"failed_msgs": {"value": int(np.count_nonzero(failed)), "limit": 0,
                            "pass_if": "<="}}


# -- what a run prints -------------------------------------------------------------


def read_metrics(cell: Cell, record: RunRecord, trace: bool) -> dict:
    """The cell's end-to-end metrics (``trace`` off) or per-layer metrics
    (on); a reader that finds nothing to read leaves its metric out."""
    out = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cell.readers[m["name"]].read(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(cell: Cell, outcome: Outcome, trace: bool) -> dict:
    record = outcome.record
    device = device_info(outcome.devices)
    device["memory_peak_bytes"] = outcome.memory_peak_bytes
    line = {"correct": outcome.correct, "attempted": int(len(record.due)),
            "failed": int(outcome.checks["failed_msgs"]["value"]),
            "metrics": read_metrics(cell, record, trace), "device": device}
    if trace and record.trace is not None:
        device["busy_s"] = record.trace.busy_s
        device["window_s"] = record.trace.window_s
        line["breakdown"] = record.trace.breakdown()
    line["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                      for k, v in outcome.checks.items()}
    return line


def notes(outcome: Outcome) -> dict:
    """What an earlier line reports: how late the generator ran, compiles
    in the window, latency even where it is no metric, batch sizes, and
    how long after the watcher's stamp the processor drained each batch."""
    from benchmarks.chip.stats import percentile, slope

    r = outcome.record
    lat = r.latency_s * 1e3
    sizes: dict[int, int] = {}
    for b in r.batches:
        sizes[len(b.messages)] = sizes.get(len(b.messages), 0) + 1
    drain = [(b.drained - b.done) * 1e3 for b in r.batches
             if np.isfinite(b.drained) and np.isfinite(b.done)]
    ts = r.t0 + np.linspace(0, r.seconds, 41)
    backlog = [np.count_nonzero(r.due <= t) - np.count_nonzero(r.done <= t) for t in ts]
    in_window = np.count_nonzero(r.done <= r.close)
    return {"notes": {
        "cell": r.cell, "seed": r.seed, "messages": int(len(r.due)),
        "setup_s": r.setup_s, "setup_phases": r.extra.get("setup_phases"),
        "reference_s": r.extra.get("reference_s"),
        "generator_late_p99_ms": percentile((r.sent - r.due) * 1e3, 99),
        "compiles_in_window": r.compiles_in_window,
        "latency_ms": {"p50": percentile(lat, 50), "p95": percentile(lat, 95),
                       "p99": percentile(lat, 99), "n": int(lat.size)},
        "completed_in_window_per_s": in_window / r.seconds,
        "due_minus_done_slope_msgs_s": slope(ts - r.t0, backlog),
        "batch_sizes": {str(k): v for k, v in sorted(sizes.items())},
        "processor_drain_after_ready_ms": {"p50": percentile(drain, 50),
                                           "p95": percentile(drain, 95),
                                           "n": len(drain)},
    }}


def check_lines(checks: dict) -> list[str]:
    return [f"check {k}: {v['value']!r} (limit {v.get('pass_if', '<=')} {v['limit']!r})"
            for k, v in checks.items()]
