"""repro.workers — the multiprocess partition execution runtime.

Fast tests cover the protocol pieces in isolation (channel correlation,
worker command round trips, heartbeat lifecycle) and a small end-to-end
``executor="mp"`` run against the inline executor. The ``slow``-marked
tests exercise the failure machinery for real: SIGKILL mid-stream with
exact recovery, hang detection via stale heartbeats, restart exhaustion,
and cross-process rescale. Bit-identical chaos comparisons live in
tests/test_chaos_rescale.py.
"""
import multiprocessing as mp
import os
import signal
import threading
import time

import numpy as np
import pytest

from repro.broker import Producer
from repro.broker.consumer import Message
from repro.core import PilotComputeService
from repro.core.failure import HeartbeatMonitor
from repro.elastic import MetricsBus
from repro.streaming import TumblingWindow
from repro.workers import (
    CONFIGURE,
    PROCESS_BATCH,
    SNAPSHOT,
    STATS,
    BatchResult,
    Reply,
    WorkerChannel,
    WorkerCrash,
    WorkerSupervisor,
    WorkerUnresponsive,
)
from repro.workers.proto import OP_APPEND, OP_OBSERVE

pytestmark = pytest.mark.skipif(
    "fork" not in mp.get_all_start_methods(),
    reason='executor="mp" requires the fork start method',
)

_CTX = mp.get_context("fork")


# -- channel ------------------------------------------------------------------


def test_channel_drops_stale_replies_and_correlates_by_seq():
    ch = WorkerChannel(_CTX)
    s1 = ch.send("A")
    s2 = ch.send("B")
    # replies arrive out of an abandoned earlier exchange first
    ch.replies.put(Reply(s1, True, "old"))
    ch.replies.put(Reply(s2, True, "new"))
    got = ch.recv(s2, timeout=5)
    assert got.payload == "new"  # stale s1 silently dropped
    ch.close()


def test_channel_drain_discards_inflight_leftovers():
    ch = WorkerChannel(_CTX)
    for i in range(3):
        ch.replies.put(Reply(i, True, BatchResult([], 0, 0.0)))
    time.sleep(0.2)  # let the feeder thread flush
    assert ch.drain() == 3
    seq = ch.send("Q")
    ch.replies.put(Reply(seq, True, "idle"))
    assert ch.recv(seq, timeout=5).payload == "idle"
    ch.close()


def test_channel_recv_raises_on_dead_and_hung_worker():
    ch = WorkerChannel(_CTX)
    seq = ch.send("X")
    with pytest.raises(WorkerCrash):
        ch.recv(seq, timeout=5, alive_fn=lambda: False)
    with pytest.raises(WorkerUnresponsive):
        ch.recv(seq, timeout=5, alive_fn=lambda: True,
                responsive_fn=lambda: False)
    with pytest.raises(WorkerUnresponsive):
        ch.recv(seq, timeout=0.2)  # hard deadline
    ch.close()


# -- heartbeat monitor lifecycle (satellite: idempotent close) ----------------


def test_monitor_close_joins_all_threads_and_is_idempotent():
    m = HeartbeatMonitor(interval=0.05, timeout=2.0)
    targets = [object() for _ in range(3)]
    for t in targets:
        m.watch(t)
    threads = list(m._agent_threads.values()) + [m._monitor]
    assert all(t.is_alive() for t in threads)
    m.close()
    assert all(not t.is_alive() for t in threads)  # joined, not leaked
    m.close()  # idempotent
    m.stop()  # legacy alias


def test_monitor_pull_based_staleness_detects_stopped_source():
    m = HeartbeatMonitor(interval=0.05, timeout=0.3)
    failed = []
    m.on_failure(failed.append)
    beat = {"t": time.monotonic()}
    target = object()
    m.watch(target, beat_fn=lambda: beat["t"])
    time.sleep(0.5)  # source keeps a stale value: no fresh stamps
    assert not m.is_alive(target)
    assert failed == [target]
    m.close()


def test_monitor_pull_based_live_source_stays_alive():
    m = HeartbeatMonitor(interval=0.05, timeout=0.3)
    target = object()
    m.watch(target, beat_fn=time.monotonic)
    time.sleep(0.5)
    assert m.is_alive(target)
    m.close()


def test_service_cancel_closes_monitor():
    svc = PilotComputeService(devices=[0, 1])
    monitor = svc.monitor
    svc.cancel()
    assert monitor._closed
    assert not monitor._monitor.is_alive()


# -- worker protocol round trip ----------------------------------------------


def _spawned(window_fn, monitor=None):
    monitor = monitor or HeartbeatMonitor(interval=0.05, timeout=1.0)
    sup = WorkerSupervisor(0, "dev0", window_fn, monitor=monitor, ctx=_CTX,
                           batch_timeout=10.0)
    return sup.spawn(), monitor


def test_worker_process_batch_snapshot_restore_stats():
    sup, monitor = _spawned(lambda k, w, msgs: (k, w, sum(float(m.value) for m in msgs)))
    try:
        assert sup.request(CONFIGURE, {"pids": [0, 1]}) == [0, 1]
        ops = [
            (OP_OBSERVE, 0, 0.5),
            (OP_APPEND, 0, "a", (0.0, 1.0), Message(0, 0, 0.5, 2.0)),
            (OP_OBSERVE, 1, 0.7),
            (OP_APPEND, 1, "b", (0.0, 1.0), Message(0, 1, 0.7, 3.0)),
        ]
        r = sup.request(PROCESS_BATCH, {"ops": ops, "watermark": 0.5})
        assert r.fired == [] and r.buffered_windows == 2  # windows still open
        r = sup.request(PROCESS_BATCH, {"ops": [], "watermark": 1.0})
        # canonical order: same window -> pid breaks the tie
        assert [(pid, key, out[2]) for pid, key, _w, out in r.fired] == [
            (0, "a", 2.0), (1, "b", 3.0)]
        stats = sup.request(STATS)
        assert stats["records"] == 2 and stats["buffered_windows"] == 0
        snap = sup.request(SNAPSHOT, {"pids": [0, 1], "release": False})
        assert set(snap) == {0, 1}  # serialized partitions came back
    finally:
        sup.stop()
        monitor.close()


def test_worker_error_propagates_without_restart():
    def bad(k, w, msgs):
        raise ValueError("deterministic user bug")

    sup, monitor = _spawned(bad)
    try:
        sup.request(CONFIGURE, {"pids": [0]})
        ops = [(OP_APPEND, 0, "k", (0.0, 1.0), Message(0, 0, 0.5, 1.0))]
        from repro.workers import WorkerError
        with pytest.raises(WorkerError, match="deterministic user bug"):
            sup.request(PROCESS_BATCH, {"ops": ops, "watermark": 2.0})
        assert sup.alive()  # the worker survives its reply
        assert sup.restarts == 0
    finally:
        sup.stop()
        monitor.close()


def test_supervisor_respawn_replaces_incarnation():
    sup, monitor = _spawned(lambda k, w, msgs: len(msgs))
    try:
        sup.request(CONFIGURE, {"pids": [0]})
        pid1 = sup.process.pid
        os.kill(pid1, signal.SIGKILL)
        sup.process.join(timeout=5)
        assert not sup.alive()
        sup.respawn()
        assert sup.alive() and sup.process.pid != pid1
        assert sup.restarts == 1
        assert sup.request(CONFIGURE, {"pids": [0]}) == [0]  # fresh + serving
    finally:
        sup.stop()
        monitor.close()


# -- engine integration (small, fast) -----------------------------------------


@pytest.fixture
def svc():
    s = PilotComputeService(devices=list(range(16)))
    yield s
    s.cancel()


def _window_fn(k, w, msgs):
    return (k, w, sum(float(m.value[0]) for m in msgs), len(msgs))


def _stream(svc, topic, *, executor, bus=None, cores=2, worker_options=None, **kw):
    kafka = svc.submit_pilot({"number_of_nodes": 1, "type": "kafka"})
    cluster = kafka.get_context()
    cluster.create_topic(topic, 1)
    flink = svc.submit_pilot(
        {"number_of_nodes": 1, "cores_per_node": cores, "type": "flink"})
    outs = []
    stream = flink.get_context().stream(
        cluster, topic, group="g",
        assigner=TumblingWindow(1.0),
        window_fn=kw.pop("window_fn", _window_fn),
        key_fn=lambda m: int(m.value[1]) % 5,
        emit=outs.append, metrics=bus, executor=executor,
        worker_options=worker_options, **kw,
    )
    return cluster, stream, outs


def _send(cluster, topic, lo, hi):
    prod = Producer(cluster, topic, serializer="npy")
    for i in range(lo, hi):
        prod.send(np.array([float(i), i]), timestamp=100.0 + i * 0.2)


def test_mp_executor_matches_inline_and_publishes_worker_gauges(svc):
    bus = MetricsBus()
    cluster, s_mp, outs_mp = _stream(
        svc, "mp1", executor="mp", bus=bus,
        worker_options={"snapshot_every": 4})
    s_mp.start()
    assert s_mp.runtime is not None and s_mp.runtime.n_workers == 2
    _send(cluster, "mp1", 0, 40)
    s_mp.await_windows(21, timeout=30)
    assert bus.value("workers.alive", stream="mp1") == 2
    assert bus.value("workers.restarts", stream="mp1") == 0
    # per-worker + aggregate latency quantiles: the loop thread publishes
    # them after the firing that woke await_windows, so poll briefly
    deadline = time.monotonic() + 5
    while (bus.value("stream.latency_p50", stream="mp1") <= 0
           and time.monotonic() < deadline):
        time.sleep(0.02)
    assert bus.value("stream.latency_p50", stream="mp1") > 0
    assert bus.value("stream.latency_p99", stream="mp1", worker="0") > 0
    s_mp.stop()
    assert bus.value("workers.alive", stream="mp1") == 0

    cluster2, s_in, outs_in = _stream(svc, "in1", executor="inline")
    s_in.start()
    _send(cluster2, "in1", 0, 40)
    s_in.await_windows(21, timeout=30)
    s_in.stop()
    assert outs_mp == outs_in  # bit-identical, including np.sum float order


def test_unknown_executor_rejected(svc):
    with pytest.raises(ValueError, match="unknown executor"):
        _stream(svc, "bad", executor="threads")


def test_mp_refuses_to_fork_from_a_process_holding_a_tpu(svc, monkeypatch):
    """Forked workers of a parent that holds the chip would hang on it:
    start() must refuse before any worker exists."""
    from jax._src import xla_bridge

    monkeypatch.setattr(xla_bridge, "backends_are_initialized", lambda: True)
    monkeypatch.setattr(xla_bridge, "backends", lambda: {"tpu": object()})
    _, stream, _ = _stream(svc, "tpuheld", executor="mp")
    with pytest.raises(RuntimeError, match="holds a TPU backend"):
        stream.start()
    assert stream.runtime is None or stream.runtime.n_workers == 0


def test_mp_rescale_drains_stale_replies_before_quiesce(svc):
    """Satellite regression: a leftover BatchResult sitting in a worker's
    reply queue (an abandoned in-flight batch) must not alias the QUIESCE
    reply — rescale drains data queues first, and the seq correlation
    would reject it anyway."""
    cluster, stream, outs = _stream(
        svc, "mpq", executor="mp", worker_options={"snapshot_every": 64})
    stream.start()
    _send(cluster, "mpq", 0, 20)
    stream.await_windows(11, timeout=30)
    for sup in stream.runtime._sups:  # forge an in-flight leftover
        sup.channel.replies.put(
            Reply(sup.channel._seq, True, BatchResult([], 99, 1.0)))
    time.sleep(0.2)  # let the queue feeder deliver the forgeries
    report = stream.rescale([0, 1, 2, 3])
    assert report is not None and report.moved
    assert stream.runtime.n_workers == 4
    _send(cluster, "mpq", 20, 40)
    stream.await_windows(21, timeout=30)
    stream.stop()
    # same totals as an uninterrupted run: the forged reply changed nothing
    assert stream.stats.records == 40
    assert [o for o in outs] == sorted(outs, key=lambda o: (o[1][1], o[1][0]))


# -- failure machinery (slow) -------------------------------------------------


@pytest.mark.slow
def test_sigkill_mid_stream_recovers_exactly(svc):
    bus = MetricsBus()
    cluster, stream, outs = _stream(
        svc, "kill", executor="mp", cores=4, bus=bus,
        worker_options={"snapshot_every": 8})
    stream.start()
    _send(cluster, "kill", 0, 30)
    stream.await_windows(10, timeout=30)
    victim = stream.runtime._sups[1]
    os.kill(victim.process.pid, signal.SIGKILL)
    _send(cluster, "kill", 30, 60)
    stream.await_windows(33, timeout=60)
    stream.stop()
    assert stream.runtime.restarts >= 1
    assert bus.value("workers.restarts", stream="kill") >= 1

    cluster2, ref, outs_ref = _stream(svc, "ref", executor="inline")
    ref.start()
    _send(cluster2, "ref", 0, 60)
    ref.await_windows(33, timeout=60)
    ref.stop()
    assert outs == outs_ref  # zero lost, zero duplicated, same order


@pytest.mark.slow
def test_hung_worker_detected_and_restarted(svc, tmp_path):
    """A window_fn wedged in user code stops stamping heartbeats; the
    supervisor flags it stale, kills the process and replays. The wedge is
    one-shot (flag file), so the replayed call completes."""
    flag = str(tmp_path / "wedged-once")

    def wedge_once(k, w, msgs):
        if not os.path.exists(flag):
            open(flag, "w").close()
            time.sleep(300)  # never stamps another beat: reads as a hang
        return (k, w, len(msgs))

    cluster, stream, outs = _stream(
        svc, "hang", executor="mp", cores=1, window_fn=wedge_once,
        worker_options={"snapshot_every": 8, "heartbeat_timeout": 0.6,
                        "heartbeat_interval": 0.05})
    stream.start()
    _send(cluster, "hang", 0, 30)
    stream.await_windows(14, timeout=60)
    stream.stop()
    assert stream.runtime.restarts == 1
    # exactly one firing per closed (key, window): the wedged call's window
    # fired once via replay, never twice
    assert len(outs) == len({o[:2] for o in outs})
    assert len(outs) >= 14


@pytest.mark.slow
def test_restart_exhaustion_surfaces_as_stream_error(svc):
    def suicide(k, w, msgs):
        os.kill(os.getpid(), signal.SIGKILL)

    cluster, stream, _ = _stream(
        svc, "die", executor="mp", cores=1, window_fn=suicide,
        worker_options={"max_restarts": 2, "snapshot_every": 8})
    stream.start()
    _send(cluster, "die", 0, 10)
    with pytest.raises(WorkerCrash, match="failed to recover"):
        stream.await_windows(1, timeout=60)
    with pytest.raises(WorkerCrash):
        stream.stop()


@pytest.mark.slow
def test_mp_rescale_moves_partitions_between_processes(svc):
    cluster, stream, outs = _stream(
        svc, "mig", executor="mp", worker_options={"snapshot_every": 64})
    stream.start()
    _send(cluster, "mig", 0, 30)
    stream.await_windows(10, timeout=30)
    pids_before = {s.process.pid for s in stream.runtime._sups}
    report = stream.rescale([10, 11, 12, 13])  # all-new owner set
    assert report.moved and len(report.moved) == stream.store.n_partitions
    pids_after = {s.process.pid for s in stream.runtime._sups}
    assert len(pids_after) == 4 and pids_before.isdisjoint(pids_after)
    _send(cluster, "mig", 30, 60)
    stream.await_windows(33, timeout=30)
    stream.stop()

    cluster2, ref, outs_ref = _stream(svc, "migref", executor="inline")
    ref.start()
    _send(cluster2, "migref", 0, 60)
    ref.await_windows(33, timeout=30)
    ref.stop()
    assert outs == outs_ref  # buffered state crossed processes losslessly
