"""Shared-memory transport: ring mechanics, frame serde, broker batch
APIs, reclaim safety, engine integration (docs/transport.md).

The zero-copy contract under test: same-host consumers read frames as
``numpy.frombuffer`` views into the ring; a view that outlives its slot
is *detected* (epoch mismatch -> SlotReclaimedError), never silently
corrupted; everything that can't ride the ring (rf>1, oversized frames,
cross-process copies) transparently falls back to copy-out with
identical results.
"""
import multiprocessing as mp
import pickle
import threading
import time

import numpy as np
import pytest

from repro.broker.cluster import BrokerCluster
from repro.broker.consumer import Consumer, ConsumerGroup
from repro.broker.log import PartitionLog
from repro.broker.producer import Producer
from repro.broker.records import Record
from repro.transport import (
    FrameBatch,
    RingTimeout,
    SharedMemoryRing,
    ShmArrayView,
    ShmTransport,
    SlotReclaimedError,
    decode_frame,
    pack_frame,
)


def shm_cluster(topic="t", *, n_parts=1, slot_bytes=1 << 20, n_slots=16,
                replication_factor=1, n_nodes=1):
    cluster = BrokerCluster(n_nodes)
    transport = ShmTransport(slot_bytes=slot_bytes, n_slots=n_slots)
    cluster.attach_transport(transport)
    cluster.create_topic(topic, n_parts, replication_factor=replication_factor)
    transport.mount(topic)
    return cluster, transport


@pytest.fixture
def shm_setup():
    cluster, transport = shm_cluster()
    yield cluster, transport
    cluster.close()


# ---------------------------------------------------------------------------
# ring mechanics
# ---------------------------------------------------------------------------


def test_ring_alloc_write_view_release_roundtrip():
    ring = SharedMemoryRing(slot_bytes=256, n_slots=4)
    try:
        slot, epoch = ring.alloc()
        assert epoch % 2 == 1  # odd = live
        assert ring.free_slots == 3
        payload = b"hello transport"
        ring.write(slot, epoch, [payload])
        assert bytes(ring.view(slot, epoch)) == payload
        ring.release(slot, epoch)
        assert ring.free_slots == 4
        assert not ring.is_valid(slot, epoch)
        with pytest.raises(SlotReclaimedError):
            ring.view(slot, epoch)
    finally:
        ring.destroy()


def test_ring_write_rejects_oversized_frames():
    ring = SharedMemoryRing(slot_bytes=16, n_slots=2)
    try:
        slot, epoch = ring.alloc()
        with pytest.raises(ValueError):
            ring.write(slot, epoch, [b"x" * 32])
    finally:
        ring.destroy()


def test_ring_exhaustion_stalls_then_times_out():
    ring = SharedMemoryRing(slot_bytes=64, n_slots=2)
    try:
        ring.alloc()
        ring.alloc()
        t0 = time.monotonic()
        with pytest.raises(RingTimeout):
            ring.alloc(deadline=time.monotonic() + 0.15)
        assert time.monotonic() - t0 >= 0.1
        assert ring.stall_seconds > 0  # backpressure is observable
    finally:
        ring.destroy()


def test_ring_reader_refcount_defers_reclaim():
    ring = SharedMemoryRing(slot_bytes=64, n_slots=2)
    try:
        slot, epoch = ring.alloc()
        ring.write(slot, epoch, [b"pinned"])
        assert ring.retain(slot, epoch)
        ring.release(slot, epoch)  # producer done, but a reader holds it
        assert ring.is_valid(slot, epoch)
        assert ring.free_slots == 1
        ring.release_ref(slot, epoch)  # last reader out -> reclaimed
        assert not ring.is_valid(slot, epoch)
        assert ring.free_slots == 2
    finally:
        ring.destroy()


def test_ring_attach_by_name_is_self_describing():
    ring = SharedMemoryRing(slot_bytes=128, n_slots=3)
    try:
        slot, epoch = ring.alloc()
        ring.write(slot, epoch, [b"cross-handle"])
        other = SharedMemoryRing.attach(ring.name)
        assert (other.slot_bytes, other.n_slots) == (128, 3)
        assert bytes(other.view(slot, epoch)) == b"cross-handle"
        other.close()
    finally:
        ring.destroy()


# ---------------------------------------------------------------------------
# frame serde
# ---------------------------------------------------------------------------


def test_frame_roundtrip_mixed_payloads():
    vals = [
        np.arange(12, dtype=np.float32).reshape(3, 4),
        np.ones((3, 4), dtype=np.float32) * 7,       # same group
        np.arange(5, dtype=np.int64),                # second group
        b"raw-bytes",                                # fallback: bytes
        np.float64(3.5),                             # fallback: 0-d
    ]
    ts = [10.0, 11.0, 12.0, 13.0, 14.0]
    frame = decode_frame(pack_frame(vals, ts, key=b"k7"))
    assert frame.timestamps == ts and frame.key == b"k7"
    assert np.array_equal(frame.values[0], vals[0])
    assert np.array_equal(frame.values[1], vals[1])
    assert np.array_equal(frame.values[2], vals[2])
    assert frame.values[3] == b"raw-bytes"
    assert float(frame.values[4]) == 3.5


def test_frame_roundtrip_structured_dtype():
    dt = np.dtype([("id", "<u4"), ("pos", "<f8", (3,)), ("flag", "?")])
    rows = np.zeros(4, dtype=dt)
    rows["id"] = [1, 2, 3, 4]
    rows["pos"] = np.arange(12).reshape(4, 3)
    rows["flag"] = [True, False, True, False]
    frame = decode_frame(pack_frame([rows, rows]))
    assert frame.values[0].dtype == dt  # dtype.str would have lost the fields
    assert np.array_equal(frame.values[1], rows)


def test_frame_zero_copy_views_alias_the_buffer():
    vals = [np.full((8,), i, dtype=np.int32) for i in range(4)]
    buf = bytearray(pack_frame(vals))
    raw = np.frombuffer(buf, dtype=np.uint8)
    zc = decode_frame(buf, zero_copy=True)
    co = decode_frame(buf, zero_copy=False)
    for v in zc.values:
        assert np.shares_memory(raw, v)  # true views, zero serde copies
    for v in co.values:
        assert not np.shares_memory(raw, v)  # default is detached copies
    for a, b in zip(zc.values, co.values):
        assert np.array_equal(a, b)


def test_zero_copy_view_across_reclaim_is_detected_not_corrupted(shm_setup):
    """Regression (ISSUE 8 satellite): a consumer holding zero-copy views
    across a slot reclaim must get an epoch-mismatch error on verify, not
    silently recycled bytes."""
    cluster, transport = shm_setup
    ring = transport.ring_for("t")
    prod = Producer(cluster, "t")
    group = ConsumerGroup(cluster, "g", "t")
    cons = Consumer(cluster, group, "m0", zero_copy=True)
    prod.send_batch([np.arange(64, dtype=np.float64)])
    [batch] = cons.poll_batch(timeout=1.0)
    view = batch.values[0]
    assert isinstance(view, ShmArrayView)
    batch.frame.verify()  # still live: fine
    cons.commit()          # advances the reclaim floor past the frame
    assert ring.free_slots == ring.n_slots, "commit should reclaim the slot"
    with pytest.raises(SlotReclaimedError):
        batch.frame.verify()
    with pytest.raises(SlotReclaimedError):
        view.verify()


# ---------------------------------------------------------------------------
# broker batch path
# ---------------------------------------------------------------------------


def test_append_many_single_batch_offsets_and_stats():
    log = PartitionLog("t", 0)
    recs = [Record(bytes([i]) * 4) for i in range(8)]
    offsets = log.append_many(recs)
    assert offsets == list(range(8))
    assert log.stats.appended_records == 8
    assert log.high_watermark == 8
    assert [r.offset for r in log.read(0, 100)] == offsets


def test_append_many_drop_policy_marks_holes():
    log = PartitionLog("t", 0, max_buffer_bytes=10, backpressure="drop")
    offsets = log.append_many([Record(b"x" * 4) for _ in range(4)])
    assert offsets == [0, 1, -1, -1]
    assert log.stats.dropped_records == 2


def test_send_batch_shm_uses_one_slot_and_tiny_records(shm_setup):
    cluster, transport = shm_setup
    ring = transport.ring_for("t")
    prod = Producer(cluster, "t")
    vals = [np.arange(256, dtype=np.float32) + i for i in range(20)]
    offsets = prod.send_batch(vals, key=b"k", timestamps=[float(i) for i in range(20)])
    assert offsets == list(range(20))
    assert ring.used_slots == 1  # 20 messages, one payload write
    log = cluster.topic("t").partitions[0]
    recs = log.read(0, 100)
    assert all(r.value[:1] == b"S" for r in recs)
    assert all(len(r.value) < 100 for r in recs)  # control plane only
    group = ConsumerGroup(cluster, "g", "t")
    cons = Consumer(cluster, group, "m0")
    msgs = cons.poll(max_records=64, timeout=1.0)
    assert len(msgs) == 20
    assert msgs[5].timestamp == 5.0
    for m, v in zip(msgs, vals):
        assert np.array_equal(m.value, v)
        assert not isinstance(m.value, ShmArrayView)  # default = copy-out


def test_send_batch_replicated_topic_copies_out():
    cluster, transport = shm_cluster("rep", replication_factor=2, n_nodes=2)
    try:
        prod = Producer(cluster, "rep")
        vals = [np.arange(16, dtype=np.int32) * i for i in range(5)]
        prod.send_batch(vals)
        assert transport.ring_for("rep").used_slots == 0  # rf>1: inline
        group = ConsumerGroup(cluster, "g", "rep")
        cons = Consumer(cluster, group, "m0")
        msgs = cons.poll(timeout=1.0)
        assert len(msgs) == 5
        for m, v in zip(msgs, vals):
            assert np.array_equal(m.value, v)
    finally:
        cluster.close()


def test_send_batch_oversized_frame_falls_back_inline():
    cluster, transport = shm_cluster("small", slot_bytes=1024)
    try:
        prod = Producer(cluster, "small")
        vals = [np.zeros(4096, dtype=np.float64)]  # 32KB >> 1KB slot
        prod.send_batch(vals)
        assert transport.ring_for("small").used_slots == 0
        group = ConsumerGroup(cluster, "g", "small")
        cons = Consumer(cluster, group, "m0")
        [m] = cons.poll(timeout=1.0)
        assert np.array_equal(m.value, vals[0])
    finally:
        cluster.close()


def test_poll_batch_groups_by_frame(shm_setup):
    cluster, _ = shm_setup
    prod = Producer(cluster, "t")
    prod.send_batch([np.ones(8, dtype=np.float32) * i for i in range(6)])
    prod.send_batch([np.ones(8, dtype=np.float32) * i for i in range(4)])
    group = ConsumerGroup(cluster, "g", "t")
    cons = Consumer(cluster, group, "m0")
    batches = cons.poll_batch(timeout=1.0, zero_copy=True)
    assert [len(b) for b in batches] == [6, 4]
    assert batches[0].offsets == list(range(6))
    assert batches[1].offsets == list(range(6, 10))
    assert float(batches[1].values[3][0]) == 3.0
    for b in batches:
        b.frame.verify()


# ---------------------------------------------------------------------------
# reclaim: commit floors, replay floors, backpressure
# ---------------------------------------------------------------------------


def test_slowest_group_pins_the_reclaim_floor(shm_setup):
    cluster, transport = shm_setup
    ring = transport.ring_for("t")
    prod = Producer(cluster, "t")
    fast = Consumer(cluster, ConsumerGroup(cluster, "fast", "t"), "f0")
    slow = Consumer(cluster, ConsumerGroup(cluster, "slow", "t"), "s0")
    for i in range(3):
        prod.send_batch([np.arange(32, dtype=np.float64) + i])
    assert ring.used_slots == 3
    fast.poll(timeout=1.0)
    fast.commit()
    # the slow group has registered but not committed: nothing reclaims
    assert ring.used_slots == 3
    slow.poll(timeout=1.0)
    slow.commit()
    assert ring.used_slots == 0


def test_replay_floor_holds_slots_past_commits(shm_setup):
    cluster, transport = shm_setup
    ring = transport.ring_for("t")
    prod = Producer(cluster, "t")
    cons = Consumer(cluster, ConsumerGroup(cluster, "g", "t"), "m0")
    # a checkpointing stream pins its replay horizon at offset 0 first
    cluster.set_replay_floor("g", "t", {0: 0})
    for i in range(3):
        prod.send_batch([np.arange(32, dtype=np.float64) + i])
    cons.poll(timeout=1.0)
    cons.commit()
    assert ring.used_slots == 3, "commit must not reclaim below the replay floor"
    # ... until the next checkpoint advances it
    cluster.set_replay_floor("g", "t", {0: 3})
    assert ring.used_slots == 0


def test_full_ring_backpressure_stalls_producer_and_feeds_io_stall():
    cluster, transport = shm_cluster("bp", slot_bytes=4096, n_slots=2)
    try:
        prod = Producer(cluster, "bp", send_timeout=5.0)
        cons = Consumer(cluster, ConsumerGroup(cluster, "g", "bp"), "m0")
        base_stall = cluster.io_stall_seconds()
        for i in range(2):
            prod.send_batch([np.arange(64, dtype=np.float64)])
        done = threading.Event()

        def produce_third():
            prod.send_batch([np.arange(64, dtype=np.float64)])
            done.set()

        t = threading.Thread(target=produce_third, daemon=True)
        t.start()
        assert not done.wait(0.3), "third batch should stall on the full ring"
        cons.poll(timeout=1.0)
        cons.commit()  # frees slots -> the stalled producer completes
        assert done.wait(5.0)
        assert cluster.io_stall_seconds() > base_stall  # elasticity signal
    finally:
        cluster.close()


def test_transport_unmount_unlinks_segment(shm_setup):
    cluster, transport = shm_setup
    name = transport.ring_for("t").name
    cluster.delete_topic("t")
    from multiprocessing import shared_memory
    with pytest.raises(FileNotFoundError):
        shared_memory.SharedMemory(name)


def test_broker_pilot_cancel_cleans_up_segments():
    from repro.core import PilotComputeService

    svc = PilotComputeService(devices=[0, 1])
    kafka = svc.submit_pilot({"number_of_nodes": 1, "type": "kafka"})
    cluster = kafka.get_context()
    transport = ShmTransport(n_slots=4)
    cluster.attach_transport(transport)
    cluster.create_topic("x", 1)
    transport.mount("x")
    name = transport.ring_for("x").name
    svc.cancel()
    from multiprocessing import shared_memory
    with pytest.raises(FileNotFoundError):
        shared_memory.SharedMemory(name)


# ---------------------------------------------------------------------------
# producer rate limiter (satellite fix)
# ---------------------------------------------------------------------------


def test_rate_limiter_sleeps_outside_the_lock(monkeypatch):
    cluster = BrokerCluster(1)
    cluster.create_topic("r", 1)
    prod = Producer(cluster, "r", rate_msgs_per_s=200.0)
    lock_held_during_sleep = []
    real_sleep = time.sleep

    def spy_sleep(seconds):
        lock_held_during_sleep.append(prod._lock.locked())

    monkeypatch.setattr(time, "sleep", spy_sleep)
    prod.send(np.zeros(4))
    prod.send(np.zeros(4))  # second send must wait for its slot
    monkeypatch.setattr(time, "sleep", real_sleep)
    assert lock_held_during_sleep, "the limiter never slept"
    assert not any(lock_held_during_sleep), (
        "rate-limit sleep while holding Producer._lock serializes all "
        "sender threads behind one sleeper")


def test_rate_limiter_paces_batches_by_element_count():
    cluster = BrokerCluster(1)
    cluster.create_topic("r", 1)
    prod = Producer(cluster, "r", rate_msgs_per_s=1000.0)
    t0 = time.monotonic()
    for _ in range(5):
        prod.send_batch([np.zeros(4) for _ in range(20)])
    # 100 msgs at 1000/s: the schedule spans >= ~80ms even though there
    # were only 5 batch calls
    assert time.monotonic() - t0 >= 0.08


# ---------------------------------------------------------------------------
# cross-process: workers attach to the segment by name
# ---------------------------------------------------------------------------


def _child_read_view(pickled, q):
    try:
        view = pickle.loads(pickled)  # reattaches the segment by name
        q.put(("sum", float(np.asarray(view).sum())))
        q.put(("valid", True))
    except SlotReclaimedError:
        q.put(("reclaimed", True))
    except Exception as exc:  # pragma: no cover
        q.put(("error", repr(exc)))


def _child_read_reclaimed(pickled, q):
    try:
        pickle.loads(pickled)
        q.put(("error", "reattach of a reclaimed slot succeeded"))
    except SlotReclaimedError:
        q.put(("reclaimed", True))
    except Exception as exc:  # pragma: no cover
        q.put(("error", repr(exc)))


def test_worker_process_attaches_view_by_name(shm_setup):
    cluster, transport = shm_setup
    prod = Producer(cluster, "t")
    cons = Consumer(cluster, ConsumerGroup(cluster, "g", "t"), "m0",
                    zero_copy=True)
    arr = np.arange(128, dtype=np.float64)
    prod.send_batch([arr])
    [batch] = cons.poll_batch(timeout=1.0)
    view = batch.values[0]
    payload = pickle.dumps(view)
    assert len(payload) < 512, "a pickled view must ship a descriptor, not bytes"
    ctx = mp.get_context("fork")
    q = ctx.Queue()
    p = ctx.Process(target=_child_read_view, args=(payload, q))
    p.start()
    p.join(10)
    results = dict(q.get(timeout=5) for _ in range(2))
    assert results.get("sum") == float(arr.sum())
    # now reclaim the slot and prove a late worker DETECTS it
    cons.commit()
    p2 = ctx.Process(target=_child_read_reclaimed, args=(payload, q))
    p2.start()
    p2.join(10)
    kind, val = q.get(timeout=5)
    assert kind == "reclaimed", val


# ---------------------------------------------------------------------------
# engines on transport="shm"
# ---------------------------------------------------------------------------


def test_microbatch_engine_processes_shm_batches_zero_copy():
    from repro.engines.microbatch import MicroBatchStream

    cluster, transport = shm_cluster("mb")
    try:
        seen = {"n": 0, "sum": 0.0, "zero_copy_values": 0}

        def process(state, msgs):
            for m in msgs:
                seen["n"] += 1
                seen["sum"] += float(np.asarray(m.value).sum())
                if isinstance(m.value, ShmArrayView):
                    seen["zero_copy_values"] += 1
            return state

        stream = MicroBatchStream(
            cluster, "mb", group="g", process_fn=process,
            batch_interval=0.05, transport="shm")
        stream.start()
        prod = Producer(cluster, "mb")
        total = 0.0
        for i in range(8):
            vals = [np.full((16,), i * 10 + j, dtype=np.float64) for j in range(10)]
            total += float(sum(v.sum() for v in vals))
            prod.send_batch(vals)
        deadline = time.monotonic() + 15
        while seen["n"] < 80 and time.monotonic() < deadline:
            time.sleep(0.02)
        stream.stop()
        assert seen["n"] == 80
        assert seen["sum"] == total
        assert seen["zero_copy_values"] == 80  # the ingest loop got views
    finally:
        cluster.close()


def test_continuous_engine_windows_identical_log_vs_shm():
    from repro.streaming import TumblingWindow
    from repro.engines.continuous import ContinuousStream

    def run(transport_mode):
        if transport_mode == "shm":
            cluster, _ = shm_cluster("cw")
        else:
            cluster = BrokerCluster(1)
            cluster.create_topic("cw", 1)
        results = {}
        stream = ContinuousStream(
            cluster, "cw", group="g", assigner=TumblingWindow(0.1),
            window_fn=lambda key, w, msgs: (key, w, float(np.sum(
                [m.value[1] for m in msgs])), len(msgs)),
            key_fn=lambda m: int(m.value[0]),
            emit=lambda out: results.__setitem__((out[0], out[1]),
                                                 (out[2], out[3])),
            transport=transport_mode,
        )
        stream.start()
        prod = Producer(cluster, "cw")
        for b in range(30):
            vals = [np.array([(b * 10 + j) % 3, float(b * 10 + j) * 1.25])
                    for j in range(10)]
            ts = [1000.0 + (b * 10 + j) * 0.01 for j in range(10)]
            prod.send_batch(vals, key=b"k", timestamps=ts)
        expected = (int(300 * 0.01 / 0.1) - 1) * 3
        stream.await_windows(expected, timeout=20)
        stream.stop()
        cluster.close()
        return results

    assert run("log") == run("shm")


# ---------------------------------------------------------------------------
# detector-simulator source + pipeline spec plumbing
# ---------------------------------------------------------------------------


def test_detector_source_batches_through_the_ring():
    from repro.miniapps import SOURCES, DetectorSimSource, SourceConfig

    assert SOURCES["detector"] is DetectorSimSource
    cluster, transport = shm_cluster("det", n_slots=32)
    try:
        src = DetectorSimSource(
            cluster, SourceConfig("det", total_messages=64),
            ny=32, nx=32, n_cached=4, frames_per_batch=16)
        src.start()
        deadline = time.monotonic() + 10
        while not src.finished and time.monotonic() < deadline:
            time.sleep(0.02)
        assert src.finished
        assert src.sent_records == 64
        log = cluster.topic("det").partitions[0]
        assert log.high_watermark == 64
        assert transport.ring_for("det").used_slots == 4  # 64/16 frames
        cons = Consumer(cluster, ConsumerGroup(cluster, "g", "det"), "m0")
        msgs = cons.poll(max_records=64, timeout=1.0)
        assert len(msgs) == 64
        assert msgs[0].value.dtype == np.uint16
        assert msgs[0].value.shape == (32, 32)
        # cache replay: frame 0 and frame 4 are the same cached payload
        assert np.array_equal(msgs[0].value, msgs[4].value)
    finally:
        src.stop()
        cluster.close()


@pytest.mark.parametrize("plane,n_groups", [("log", 1), ("log", 4), ("shm", 1), ("shm", 4)])
def test_detector_fan_out_every_group_receives_every_frame(plane, n_groups):
    """Independent consumer groups drain one detector stream concurrently:
    each receives every frame once, in order, with its payload, on either
    data plane. The shm ring holds far fewer trains than the stream sends,
    so the producer only gets through as every group commits."""
    from repro.miniapps import DetectorSimSource, SourceConfig

    n_msgs = 512
    cluster = BrokerCluster(1)
    if plane == "shm":
        cluster.attach_transport(ShmTransport(slot_bytes=1 << 20, n_slots=4))
    cluster.create_topic("frames", 1)
    if plane == "shm":
        cluster.transport.mount("frames")
    # groups register before the stream starts: a registered group with no
    # progress holds every slot, so no group can miss a frame
    consumers = [Consumer(cluster, ConsumerGroup(cluster, f"g{i}", "frames"),
                          f"m{i}", zero_copy=(plane == "shm"))
                 for i in range(n_groups)]
    src = DetectorSimSource(cluster, SourceConfig("frames", total_messages=n_msgs),
                            ny=32, nx=32, n_cached=4, frames_per_batch=32)
    seen = [[] for _ in consumers]

    def drain(consumer, out):
        deadline = time.monotonic() + 60
        while len(out) < n_msgs and time.monotonic() < deadline:
            for m in consumer.poll(max_records=128, timeout=0.5):
                # read the payload before the commit lets its slot go
                out.append((m.offset, np.array_equal(m.value, src._cache[m.offset % 4])))
            consumer.commit()

    threads = [threading.Thread(target=drain, args=(c, out), daemon=True)
               for c, out in zip(consumers, seen)]
    try:
        for t in threads:
            t.start()
        src.start()
        for t in threads:
            t.join(timeout=90)
        for out in seen:
            assert [off for off, _ in out] == list(range(n_msgs))
            assert all(ok for _, ok in out)
        assert cluster.lost_records == 0
    finally:
        src.stop()
        for c in consumers:
            c.close()
        cluster.close()


def test_detector_source_hdf5_input(tmp_path):
    h5py = pytest.importorskip("h5py")
    from repro.miniapps import DetectorSimSource, SourceConfig

    path = tmp_path / "frames.h5"
    frames = np.arange(3 * 8 * 8, dtype=np.uint16).reshape(3, 8, 8)
    with h5py.File(path, "w") as f:
        f.create_dataset("frames", data=frames)
    cluster, _ = shm_cluster("h5")
    try:
        src = DetectorSimSource(
            cluster, SourceConfig("h5", total_messages=3),
            hdf5_path=str(path), n_cached=8, frames_per_batch=3)
        src.start()
        deadline = time.monotonic() + 10
        while not src.finished and time.monotonic() < deadline:
            time.sleep(0.02)
        cons = Consumer(cluster, ConsumerGroup(cluster, "g", "h5"), "m0")
        msgs = cons.poll(max_records=8, timeout=1.0)
        assert len(msgs) == 3
        for m, f in zip(msgs, frames):
            assert np.array_equal(m.value, f)
    finally:
        src.stop()
        cluster.close()


def test_pipeline_spec_roundtrips_transport_fields():
    from repro.pipeline import Pipeline, PipelineSpec

    spec = (
        Pipeline.named("shm-pipe")
        .broker(nodes=1, transport="shm",
                transport_options={"slot_bytes": 1 << 16, "n_slots": 8})
        .topic("frames", partitions=1)
        .source("frames", kind="detector", total_messages=10)
        .stage("agg", topic="frames", processor=lambda state, msgs: state,
               transport="shm")
        .build()
    )
    assert spec.broker.transport == "shm"
    assert spec.broker.transport_options == {"slot_bytes": 1 << 16, "n_slots": 8}
    assert spec.stage("agg").transport == "shm"
    back = PipelineSpec.from_dict(spec.to_dict())
    assert back == spec


def test_builder_rejects_bad_transport_combinations():
    from repro.pipeline import Pipeline, PipelineValidationError

    with pytest.raises(PipelineValidationError) as exc:
        (
            Pipeline.named("bad")
            .broker(transport="carrier-pigeon")
            .topic("x", partitions=1)
            .stage("s", topic="x", processor=lambda st, ms: st,
                   transport="shm")
            .build()
        )
    msg = str(exc.value)
    assert "carrier-pigeon" in msg
    assert "requires the broker" in msg
