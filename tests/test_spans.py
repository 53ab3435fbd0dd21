"""Spans on the micro-batch path (``repro.elastic.metrics.span``): a profiler
trace of a tiny gridrec pipeline (kernels interpreted on the CPU) holds
each layer's span, nested as the layers nest and carrying the engine's
batch id; with no profiler on, a span is one shared no-op. The jitted
programs carry stable names, and the engine's batch records count the
bytes their polls consumed."""
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.broker.producer import Producer
from repro.elastic import metrics as metrics_mod
from repro.elastic.metrics import NO_SPAN, BatchMetrics, span
from repro.miniapps.masa import ReconstructionApp, StreamingKMeans
from repro.pipeline import Pipeline

ANGLES, DET = 16, 32
PROGRAM_SPANS = ("consumer.poll", "engine.collect", "engine.process", "engine.commit",
                 "app.prep", "app.dispatch", "app.wait")
#: an idle engine no longer sleeps between windows; the trace is read for it
#: to show that none is left
GONE_SPANS = ("engine.idle",)


def _pipeline():
    spec = (Pipeline.named("spans")
            .broker(nodes=1, transport="shm",
                    transport_options={"slot_bytes": 3 * ANGLES * DET * 4 + 4096, "n_slots": 8})
            .topic("frames", partitions=1)
            .stage("s", topic="frames", processor="gridrec", transport="shm",
                   batch_interval=0.02, max_batch_records=4, n=32, use_kernel=True)
            .build())
    return spec.run().start()


def _frames(n):
    rng = np.random.default_rng(0)
    return [rng.normal(size=(ANGLES, DET)).astype(np.float32) for _ in range(n)]


def _feed(run, frames, bursts):
    """Send ``bursts`` (frames each, one ``send_batch`` a burst, so that a
    poll returns the burst whole and the engine, whose window closes as soon
    as the idle processor holds a record, batches it whole), then wait for
    them."""
    stream = run.stream("s")
    producer = Producer(run.cluster, "frames")
    want = stream.stats.records + sum(bursts)
    for size in bursts:
        producer.send_batch(frames[:size])
        time.sleep(0.06)
    deadline = time.monotonic() + 60
    while stream.stats.records < want and time.monotonic() < deadline:
        time.sleep(0.01)
    assert stream.stats.records == want


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The program's spans in a traced stretch of 7 frames, by name: (host
    line, start, end, stats) each."""
    run = _pipeline()
    frames = _frames(3)
    try:
        _feed(run, frames, [1, 2])  # compiles both programs outside the trace
        out = tmp_path_factory.mktemp("trace")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(out), profiler_options=opts)
        try:
            time.sleep(0.1)  # the window open at the start goes untraced
            _feed(run, frames, [1, 3, 1, 2])
            time.sleep(0.1)  # empty windows: the engine idles
        finally:
            jax.profiler.stop_trace()
    finally:
        run.stop()
    (path,) = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
    spans: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in PROGRAM_SPANS + GONE_SPANS:
                    spans.setdefault(e.name, []).append(
                        (i, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return spans


def _inside(inner, outer):
    return inner[0] == outer[0] and outer[1] <= inner[1] and inner[2] <= outer[2]


def _parent(s, candidates):
    found = [c for c in candidates if _inside(s, c)]
    assert len(found) == 1, (s, found)
    return found[0]


def test_every_span_is_traced(traced):
    assert set(traced) == set(PROGRAM_SPANS)
    assert not any(name in traced for name in GONE_SPANS)
    assert {s[3]["woke"] for s in traced["consumer.poll"]} <= {"none", "data", "deadline"}
    for name in ("engine.collect", "engine.process", "engine.commit"):
        assert all(isinstance(s[3].get("batch"), int) for s in traced[name]), name
    assert {s[3]["program"] for s in traced["app.dispatch"]} == {"gridrec_frame", "gridrec_stack"}


def test_collect_says_why_it_closed(traced):
    closed = [s[3]["closed"] for s in traced["engine.collect"]]
    assert set(closed) <= {"idle", "interval", "full"}
    # gridrec is record-wise: a burst into the drained engine closes early
    assert "idle" in closed
    assert all(s[3]["closed"] == "interval" for s in traced["engine.collect"]
               if s[3]["records"] == 0)


def test_spans_nest_as_the_layers(traced):
    polled = 0
    for s in traced["consumer.poll"]:
        _parent(s, traced["engine.collect"])
        polled += s[3]["records"]
    assert polled == sum(c[3]["records"] for c in traced["engine.collect"]) == 7
    processes = traced["engine.process"]
    for name in ("app.prep", "app.dispatch"):
        for s in traced[name]:
            _parent(s, processes)
    for s in traced["app.wait"]:  # the engine's; the stop's sync is outside
        if any(p[0] == s[0] for p in processes):
            _parent(s, processes)
    for p in processes:
        batch = p[3]["batch"]
        collect = [c for c in traced["engine.collect"] if c[3]["batch"] == batch
                   and c[3]["records"] > 0]
        commit = [c for c in traced["engine.commit"] if c[3]["batch"] == batch]
        assert len(collect) == len(commit) == 1
        assert collect[0][2] <= p[1] and p[2] <= commit[0][1]
        assert collect[0][3]["records"] == p[3]["records"]
        prep = [s for s in traced["app.prep"] if _inside(s, p)]
        assert len(prep) == 1 and prep[0][3]["frames"] == p[3]["records"]
    assert sum(p[3]["records"] for p in processes) == 7


def test_no_profiler_no_annotation(monkeypatch):
    made = []

    class Counting(metrics_mod.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(metrics_mod, "TraceAnnotation", Counting)
    assert span("engine.process", batch=1) is NO_SPAN
    with span("consumer.poll") as s:
        s.set_metadata(records=3)
    assert s is NO_SPAN
    run = _pipeline()
    try:
        _feed(run, _frames(2), [1, 2])
    finally:
        run.stop()
    assert made == []


def test_batch_metrics_count_consumed_bytes():
    run = _pipeline()
    try:
        _feed(run, _frames(3), [1, 3])
        stats = run.stream("s").stats
    finally:
        run.stop()
    assert sum(h.n_records for h in stats.history) == 4
    assert [h.bytes for h in stats.history] == [h.n_records * ANGLES * DET * 4
                                                for h in stats.history]
    assert stats.bytes == 4 * ANGLES * DET * 4
    assert not hasattr(BatchMetrics(1, 1, 1, 0.0, 0.0), "end_to_end_latency")


@pytest.mark.parametrize("algorithm", ["gridrec", "mlem"])
def test_reconstruction_programs_have_stable_names(algorithm):
    app = ReconstructionApp(algorithm, n=32, mlem_iters=1, use_kernel=True)
    angles = jnp.linspace(0, jnp.pi, ANGLES, endpoint=False)
    frame = app._rec.lower(jnp.ones((ANGLES, DET)), angles).as_text()
    stack = app._rec_batch.lower(jnp.ones((2, ANGLES, DET)), angles).as_text()
    assert frame.startswith(f"module @jit_{algorithm}_frame ")
    assert stack.startswith(f"module @jit_{algorithm}_stack ")
    assert "backproject_pallas" in frame and "backproject_pallas" in stack


def test_kmeans_program_has_a_stable_name():
    app = StreamingKMeans(10, 3, use_kernel=True)
    text = app._step.lower(jnp.ones((512, 3)), app.centroids, 5).as_text()
    assert text.startswith("module @jit_kmeans_step ")
    assert "assign_pallas" in text
