"""Engines: micro-batch exactly-once, PID backpressure, the record-wise
window close, continuous windows, taskpool speculative execution, pilot
lifecycle + failure recovery."""
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from repro.broker import BrokerCluster, Producer
from repro.core import CUState, PilotComputeDescription, PilotComputeService
from repro.engines.microbatch import MicroBatchStream
from repro.miniapps import LMServeApp, LMTrainApp, ReconstructionApp, StreamingKMeans
from repro.streaming import PIDRateController, TumblingWindow

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def svc():
    s = PilotComputeService()
    yield s
    s.cancel()


def _broker(svc, topics):
    pilot = svc.submit_pilot({"number_of_nodes": 1, "type": "kafka"})
    cluster = pilot.get_context()
    for t, p in topics:
        cluster.create_topic(t, p)
    return pilot, cluster


def test_pilot_startup_and_states(svc):
    pilot = svc.submit_pilot({"number_of_nodes": 1, "type": "dask"})
    assert pilot.state.value == "Running"
    assert pilot.startup_time is not None and pilot.startup_time < 5


def test_exactly_once_replay_after_crash(svc):
    """Crash between checkpoint and failure: recovery rewinds to committed
    offsets and recomputes the same state."""
    _, cluster = _broker(svc, [("t", 2)])
    spark = svc.submit_pilot({"number_of_nodes": 1, "type": "spark"})
    ctx = spark.get_context()
    prod = Producer(cluster, "t", serializer="npy")
    for i in range(16):
        prod.send(np.array([float(i)]))

    checkpoints = []

    def ckpt(state, offsets):
        checkpoints.append((state, dict(offsets)))

    def process(state, msgs):
        return (state or 0.0) + sum(float(m.value[0]) for m in msgs)

    s = ctx.stream(cluster, "t", group="g", process_fn=process, batch_interval=0.02,
                   max_batch_records=4, backpressure=False, checkpoint_fn=ckpt)
    s.start()
    s.await_batches(4, timeout=20)
    s.stop()
    final = s.state

    # simulate crash + recovery from the SECOND checkpoint: replay the rest
    state, offsets = checkpoints[1]
    s2 = ctx.stream(cluster, "t", group="g2", process_fn=process, batch_interval=0.02,
                    max_batch_records=4, backpressure=False)
    s2.recover(state, offsets)
    s2.start()
    deadline = time.monotonic() + 20
    while sum(s2.lag().values()) > 0 and time.monotonic() < deadline:
        time.sleep(0.05)
    s2.stop()
    assert s2.state == final == sum(range(16))


def test_pid_controller_reduces_rate_under_overload():
    pid = PIDRateController(batch_interval=0.1)
    r1 = pid.update(n_records=1000, processing_delay=0.1)  # at capacity
    r2 = pid.update(n_records=1000, processing_delay=0.4)  # 4x overloaded
    assert r2 < r1
    assert pid.max_records_per_batch < 1000


# ---------------------------------------------------------------------------
# the window of a record-wise processor closes once its device has drained
# ---------------------------------------------------------------------------


class _RecordWise:
    """A record-wise processor whose device the test drives through ``busy``."""

    record_wise = True

    def __init__(self, busy=False):
        self.busy = busy
        self.batches: list[list[float]] = []
        self.started: list[float] = []

    def process(self, state, msgs):
        self.started.append(time.monotonic())
        self.batches.append([float(np.asarray(m.value).flat[0]) for m in msgs])
        return state


def _microbatch(process_fn, interval, **kw):
    cluster = BrokerCluster(1)
    cluster.create_topic("t", 1)
    stream = MicroBatchStream(cluster, "t", group="g", process_fn=process_fn,
                              batch_interval=interval, backpressure=False, **kw)
    return cluster, stream, Producer(cluster, "t", serializer="npy")


def test_record_wise_window_closes_once_the_device_is_idle():
    app = _RecordWise()
    cluster, stream, prod = _microbatch(app.process, 2.0)
    stream.start()
    try:
        time.sleep(0.05)
        sent = time.monotonic()
        prod.send(np.array([1.0]))
        stream.await_batches(1, timeout=1.5)  # the clock would hold it to 2 s
    finally:
        stream.stop()
        cluster.close()
    assert app.batches == [[1.0]]
    assert app.started[0] - sent < 0.5
    assert stream.stats.idle_closes == 1


@pytest.mark.parametrize("drains", [True, False], ids=["drained", "interval"])
def test_records_held_while_busy_come_out_as_one_batch(drains):
    """While the device is busy the window gathers records as the clock
    window does; it closes once the device drains, or at the interval."""
    app = _RecordWise(busy=True)
    interval = 2.0 if drains else 0.4
    cluster, stream, prod = _microbatch(app.process, interval)
    stream.start()
    start = time.monotonic()
    try:
        for i in range(3):
            prod.send(np.array([float(i)]))
            time.sleep(0.01)
        time.sleep(0.05)
        assert app.batches == []
        app.busy = not drains
        stream.await_batches(1, timeout=5)
    finally:
        stream.stop()
        cluster.close()
    assert app.batches == [[0.0, 1.0, 2.0]]
    if drains:
        assert app.started[0] - start < 1.0
        assert stream.stats.idle_closes == 1
    else:
        assert app.started[0] - start >= interval - 0.05
        assert stream.stats.idle_closes == 0


def test_record_after_an_empty_window_is_processed_at_once(monkeypatch):
    """An empty window ends in the consumer's blocking poll and the next
    opens at once: no idle sleep (and no ``engine.idle`` span) lies between
    them, and the append itself wakes the poll."""
    import repro.broker.consumer as consumer_mod
    import repro.engines.microbatch as microbatch_mod

    spans = []

    def span(name, **stats):
        class _Span:
            def __enter__(self):
                return self

            def set_metadata(self, **more):
                stats.update(more)

            def __exit__(self, *exc):
                spans.append((name, stats, time.monotonic()))

        return _Span()

    monkeypatch.setattr(consumer_mod, "span", span)
    monkeypatch.setattr(microbatch_mod, "span", span)
    app = _RecordWise()
    cluster, stream, prod = _microbatch(app.process, 0.05)
    stream.start()
    try:
        deadline = time.monotonic() + 5
        while not any(n == "engine.collect" and st["records"] == 0
                      for n, st, _ in spans):
            assert time.monotonic() < deadline
            time.sleep(0.001)
        sent = time.monotonic()
        prod.send(np.array([1.0]))
        stream.await_batches(1, timeout=5)
        time.sleep(0.15)  # more empty windows
    finally:
        stream.stop()
        cluster.close()
    assert app.batches == [[1.0]]
    assert app.started[0] - sent < 0.03
    names = {n for n, _, _ in spans}
    assert "engine.idle" not in names
    assert sum(n == "engine.collect" and st["records"] == 0 for n, st, _ in spans) >= 3
    [woke] = [st["woke"] for n, st, _ in spans if n == "consumer.poll" and st["records"]]
    assert woke == "data"


def test_max_batch_records_still_caps_a_record_wise_window():
    app = _RecordWise()
    cluster, stream, prod = _microbatch(app.process, 2.0, max_batch_records=2)
    prod.send_batch([np.array([float(i)]) for i in range(5)])
    stream.start()
    try:
        stream.await_batches(3, timeout=1.5)
    finally:
        stream.stop()
        cluster.close()
    assert app.batches == [[0.0, 1.0], [2.0, 3.0], [4.0]]
    assert stream.stats.idle_closes == 1  # the first two closed full


def _kmeans():
    app = StreamingKMeans(n_clusters=2, dim=1)
    return app.process, {}


def _plain_callable():
    return (lambda state, msgs: state), {}


def _checkpointing():
    return _RecordWise().process, {"checkpoint_fn": lambda state, offsets: None}


@pytest.mark.parametrize("make", [_kmeans, _plain_callable, _checkpointing],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_other_streams_keep_the_clock_window(make):
    """Records 10 ms apart inside one window land in one batch: KMeans folds
    a batch into its centroids, a plain callable declares nothing, and a
    checkpointing stream would snapshot every extra batch."""
    process_fn, kw = make()
    cluster, stream, prod = _microbatch(process_fn, 0.3, **kw)
    stream.start()
    try:
        for i in range(3):
            prod.send(np.array([[float(i)]]))
            time.sleep(0.01)
        stream.await_batches(1, timeout=30)
    finally:
        stream.stop()
        cluster.close()
    assert [b.n_records for b in stream.stats.history] == [3]
    assert stream.stats.idle_closes == 0


def test_only_reconstruction_declares_record_wise():
    assert ReconstructionApp.record_wise is True
    for cls in (StreamingKMeans, LMTrainApp, LMServeApp):
        assert cls.record_wise is False, cls


class _Collector:
    def on_ready(self, batch, result, outputs):
        pass


def test_record_wise_window_closes_through_the_benchmark_wrapper():
    """The benchmark wraps the processor in ``TimedProcessor``, which
    forwards ``record_wise`` and ``busy`` to the app inside."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from benchmarks.chip.harness import Recorder, TimedProcessor

    app = ReconstructionApp("gridrec", n=16)
    recorder = Recorder(_Collector())
    timed = TimedProcessor(app, recorder)
    assert timed.record_wise is True and timed.busy is False
    sent = time.time()
    recorder.arm(np.array([sent]))
    cluster, stream, prod = _microbatch(timed.process, 2.0, deserialize=True)
    assert stream.sync_fn == timed.sync
    stream.start()
    try:
        t0 = time.monotonic()
        prod.send(np.ones((8, 16), np.float32), timestamp=sent)
        stream.await_batches(1, timeout=30)
    finally:
        stream.stop()
        cluster.close()
        recorder.close()
    assert recorder.batches[0].start - t0 < 0.5
    assert stream.stats.idle_closes == 1
    assert timed.busy is False and app.in_flight == 0


def test_continuous_event_time_windows(svc):
    _, cluster = _broker(svc, [("ev", 1)])
    flink = svc.submit_pilot({"number_of_nodes": 1, "type": "flink"})
    ctx = flink.get_context()
    outputs = []

    def window_fn(key, window, msgs):
        return (window, sum(float(m.value[0]) for m in msgs))

    s = ctx.stream(cluster, "ev", group="w", assigner=TumblingWindow(10.0),
                   window_fn=window_fn, emit=outputs.append)
    s.start()
    prod = Producer(cluster, "ev", serializer="npy")
    base = 1000.0
    for ts, v in [(1, 1.0), (2, 2.0), (11, 10.0), (3, 99.0), (25, 5.0)]:
        prod.send(np.array([v]), timestamp=base + ts)
    s.await_windows(2, timeout=20)
    s.stop()
    # window [1000,1010) fired with 1+2 (+99 if not late: watermark only moved
    # to 1011 when (11,10.0) arrived -> (3,99.0) is NOT late with lateness=0? it is: 1003 < 1011
    fired = {tuple(np.round(w, 1)): v for (w, v) in outputs}
    assert fired[(1000.0, 1010.0)] == 3.0
    assert fired[(1010.0, 1020.0)] == 10.0
    assert s.stats.late_records == 1


def test_taskpool_speculative_execution(svc):
    pilot = svc.submit_pilot({"number_of_nodes": 1, "cores_per_node": 4, "type": "dask",
                              "speculative_multiple": 2.0})
    plugin = pilot.get_context()
    state = {"hung": True}

    def quick(i):
        time.sleep(0.02)
        return i

    def straggler():
        # first attempt hangs; the speculative duplicate returns immediately
        if state.pop("hung", None):
            time.sleep(30)
            return "slow"
        return "fast"

    cus = [pilot.submit(quick, i) for i in range(8)]
    for cu in cus:
        cu.wait(10)
    slow_cu = pilot.submit(straggler)
    assert slow_cu.wait(15) == "fast"
    assert plugin.speculated >= 1
    assert slow_cu.attempts >= 2


def test_taskpool_extend_and_shrink(svc):
    pilot = svc.submit_pilot({"number_of_nodes": 1, "cores_per_node": 2, "type": "dask"})
    plugin = pilot.get_context()
    assert plugin.n_workers == 2
    ext = svc.submit_pilot(PilotComputeDescription(number_of_nodes=1, cores_per_node=2,
                                                   framework="dask", parent=pilot))
    assert plugin.n_workers == 4
    ext.cancel()
    assert plugin.n_workers == 2


def test_cu_failure_propagates(svc):
    pilot = svc.submit_pilot({"number_of_nodes": 1, "type": "dask"})

    def boom():
        raise ValueError("exploded")

    cu = pilot.submit(boom)
    with pytest.raises(ValueError, match="exploded"):
        cu.wait(10)
    assert cu.state == CUState.FAILED


def test_broker_failure_keeps_pipeline_alive(svc):
    kafka = svc.submit_pilot({"number_of_nodes": 2, "type": "kafka"})
    cluster = kafka.get_context()
    cluster.create_topic("t", 4)
    ext = svc.submit_pilot(PilotComputeDescription(number_of_nodes=1, framework="kafka",
                                                   parent=kafka))
    n_before = cluster.n_nodes
    svc.inject_failure(ext)  # involuntary shrink
    assert cluster.n_nodes == n_before - 1
    prod = Producer(cluster, "t", serializer="raw")
    assert prod.send(b"still alive") >= 0


def test_continuous_async_emit_equivalent_and_exactly_once_after_crash():
    """The emit double-buffer (docs/perf.md): same fired windows, same
    delivered outputs as the synchronous path — including across a crash
    (pending emits are discarded and re-fired by the replay exactly once)."""
    from repro.engines.continuous import ContinuousStream

    def run(async_emit, crash_at=None):
        cluster = BrokerCluster(1)
        cluster.create_topic("t", 1)
        results = []
        stream = ContinuousStream(
            cluster, "t", group="g", assigner=TumblingWindow(0.1),
            window_fn=lambda key, w, msgs: (key, w, float(np.sum(
                [m.value[1] for m in msgs])), len(msgs)),
            key_fn=lambda m: int(m.value[0]) % 3,
            emit=results.append,
            checkpoint_every=40,
            async_emit=async_emit,
        )
        assert (stream._emit_window is not None) == (async_emit > 0)
        stream.start()
        prod = Producer(cluster, "t")
        for b in range(30):
            vals = [np.array([(b * 10 + j) % 3, float(b * 10 + j) * 1.25])
                    for j in range(10)]
            ts = [1000.0 + (b * 10 + j) * 0.01 for j in range(10)]
            prod.send_batch(vals, timestamps=ts)
            if crash_at is not None and b == crash_at:
                time.sleep(0.15)
                stream.crash()
                stream.recover()
        # ~29 full windows x 3 keys fire; the last partial ones never do
        stream.await_windows(80, timeout=20)
        time.sleep(0.2)
        stream.stop()
        assert stream.stats.fired_windows == len(results)
        cluster.close()
        return sorted(results)

    sync_out = run(0)
    async_out = run(3)
    assert async_out == sync_out
    crashed = run(3, crash_at=18)
    assert len(crashed) == len(set(crashed)), "duplicated window delivery"
    assert crashed == sync_out
