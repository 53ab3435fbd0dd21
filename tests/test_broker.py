"""Broker semantics: logs, offsets, consumer groups, backpressure, serde."""
import threading
import time

import numpy as np
import pytest

# property tests need hypothesis (requirements-dev.txt); the plain unit
# tests below must keep running without it
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    given = settings = st = None

from repro.broker import (
    BackpressureError,
    BrokerCluster,
    Consumer,
    ConsumerGroup,
    PartitionLog,
    Producer,
    Record,
    decode_array,
    decode_msg,
    encode_array,
    encode_msg,
)


def test_partition_log_offsets_monotonic():
    log = PartitionLog("t", 0)
    offs = [log.append(Record(b"x" * 10)) for _ in range(100)]
    assert offs == list(range(100))
    assert log.high_watermark == 100
    recs = log.read(10, max_records=5)
    assert [r.offset for r in recs] == [10, 11, 12, 13, 14]


def test_partition_log_retention_trims_oldest():
    log = PartitionLog("t", 0, max_buffer_bytes=1000, retention_bytes=100)
    for _ in range(50):
        log.append(Record(b"x" * 10))
    assert log.earliest > 0
    assert log.buffered_bytes <= 100
    # reads below the earliest offset clamp forward
    recs = log.read(0, max_records=5)
    assert recs[0].offset == log.earliest


def test_backpressure_block_then_drain():
    log = PartitionLog("t", 0, max_buffer_bytes=100, backpressure="block")
    for _ in range(10):
        log.append(Record(b"x" * 10))
    done = []

    def producer():
        log.append(Record(b"y" * 10), timeout=5)
        done.append(1)

    t = threading.Thread(target=producer)
    t.start()
    time.sleep(0.1)
    assert not done  # blocked
    log.ack(5)  # consumer frees space
    t.join(2)
    assert done
    assert log.stats.blocked_seconds > 0


def test_backpressure_error_policy():
    log = PartitionLog("t", 0, max_buffer_bytes=50, backpressure="error")
    for _ in range(5):
        log.append(Record(b"x" * 10))
    with pytest.raises(BackpressureError):
        log.append(Record(b"x" * 10))


def test_consumer_group_rebalance_covers_all_partitions():
    cluster = BrokerCluster(2)
    cluster.create_topic("t", 7)
    g = ConsumerGroup(cluster, "g", "t")
    c1 = Consumer(cluster, g, "a")
    c2 = Consumer(cluster, g, "b")
    c3 = Consumer(cluster, g, "c")
    parts = c1.assignment + c2.assignment + c3.assignment
    assert sorted(parts) == list(range(7))  # partition of the partitions
    c2.close()
    parts = c1.assignment + c3.assignment
    assert sorted(parts) == list(range(7))


def test_commit_and_rewind_exactly_once_semantics():
    cluster = BrokerCluster(1)
    cluster.create_topic("t", 2)
    prod = Producer(cluster, "t", serializer="raw")
    for i in range(20):
        prod.send(bytes([i]))
    g = ConsumerGroup(cluster, "g", "t")
    c = Consumer(cluster, g, "m", deserialize=False)
    first = c.poll(10)
    c.commit()
    second = c.poll(10)
    # crash before committing the second poll -> rewind replays it
    c.rewind_to_committed()
    replay = c.poll(10)
    assert [m.value for m in replay] == [m.value for m in second]


def test_elastic_node_add_remove_and_failure():
    cluster = BrokerCluster(1)
    cluster.create_topic("t", 4)
    n0 = cluster.n_nodes
    nid = cluster.add_node()
    assert cluster.n_nodes == n0 + 1
    cluster.fail_node(nid)
    assert cluster.n_nodes == n0
    # data still reachable after failover
    prod = Producer(cluster, "t", serializer="raw")
    assert prod.send(b"alive") >= 0


def test_replicated_topic_places_replicas_on_distinct_nodes():
    cluster = BrokerCluster(3)
    t = cluster.create_topic("t", 4, replication_factor=2)
    for p in range(4):
        holders = t.holders(p)
        assert len(holders) == 2 == len(set(holders))
        assert holders[0] == t.leaders[p]
    # the list-of-logs view resolves to the leader copies
    assert [log.partition for log in t.partitions] == [0, 1, 2, 3]


def test_fail_node_promotes_follower_without_acked_loss():
    cluster = BrokerCluster(3)
    cluster.create_topic("t", 2, replication_factor=2)
    prod = Producer(cluster, "t", serializer="raw")
    for _ in range(40):
        prod.send(b"v")  # round-robins both partitions
    dead = cluster.topic("t").leaders[0]
    cluster.fail_node(dead)
    assert cluster.failovers >= 1
    assert cluster.lost_records == 0
    # every partition still serves its whole log from a promoted leader
    total = sum(len(cluster.read("t", p, 0, 1000)) for p in range(2))
    assert total == 40
    # and the rebalance restored the replication factor on the survivors
    t = cluster.topic("t")
    for p in range(2):
        assert len(t.replicas[p]) == 2
        assert dead not in t.replicas[p]
        follower = [n for n in t.replicas[p] if n != t.leaders[p]][0]
        assert (t.replicas[p][follower].high_watermark
                == t.leader_log(p).high_watermark)


def test_fail_node_unreplicated_loses_records_but_offsets_stay_monotonic():
    cluster = BrokerCluster(2)
    cluster.create_topic("t", 1, replication_factor=1)
    prod = Producer(cluster, "t", serializer="raw")
    for _ in range(30):
        prod.send(b"x")
    cluster.fail_node(cluster.topic("t").leaders[0])
    assert cluster.lost_records == 30
    # the partition restarts empty at the old high watermark: the next send
    # continues the offset sequence instead of reusing burned offsets
    assert prod.send(b"y") == 30
    recs = cluster.read("t", 0, 0, 100)
    assert [r.offset for r in recs] == [30]


def test_consumer_group_generation_bumps_after_node_loss():
    cluster = BrokerCluster(2)
    cluster.create_topic("t", 2, replication_factor=2)
    g = ConsumerGroup(cluster, "g", "t")
    c = Consumer(cluster, g, "m")
    assert c.assignment == [0, 1]
    gen = g.generation
    cluster.fail_node(cluster.topic("t").leaders[0])
    assert g.generation > gen  # members re-sync on their next poll
    assert c.assignment == [0, 1]


def test_committed_offsets_survive_failover():
    cluster = BrokerCluster(2)
    cluster.create_topic("t", 1, replication_factor=2)
    prod = Producer(cluster, "t", serializer="raw")
    for i in range(20):
        prod.send(bytes([i % 3]))
    g = ConsumerGroup(cluster, "g", "t")
    c = Consumer(cluster, g, "m", deserialize=False)
    first = c.poll(10)
    assert len(first) == 10
    c.commit()
    cluster.fail_node(cluster.topic("t").leaders[0])
    assert cluster.committed("g", "t", 0) == 10
    # a restarted member resumes exactly at the commit on the new leader
    c2 = Consumer(cluster, ConsumerGroup(cluster, "g2", "t"), "m2", deserialize=False)
    c2.seek(0, cluster.committed("g", "t", 0))
    replay = c2.poll(100)
    assert [m.offset for m in replay] == list(range(10, 20))


# ---------------------------------------------------------------------------
# a waiting poll blocks on the topic's append sequence, not on a sleep
# ---------------------------------------------------------------------------


class _SpanLog:
    """Stands in for ``repro.elastic.metrics.span``: keeps each span's name
    and final stats, as a profiler trace would."""

    def __init__(self):
        self.ended: list[tuple[str, dict]] = []

    def __call__(self, name, **stats):
        log = self

        class _Span:
            def __enter__(self):
                return self

            def set_metadata(self, **more):
                stats.update(more)

            def __exit__(self, *exc):
                log.ended.append((name, stats))

        return _Span()


def _waiting_consumer(n_parts, **kw):
    cluster = BrokerCluster(kw.pop("nodes", 1))
    cluster.create_topic("t", n_parts, **kw)
    return cluster, Consumer(cluster, ConsumerGroup(cluster, "g", "t"), "m",
                             deserialize=False)


def _poll_in_thread(call):
    """Run ``call`` in a thread; the returned dict gets its result (or
    error) and the monotonic instant it returned."""
    done: dict = {}

    def run():
        try:
            done["out"] = call()
        except BaseException as e:  # surfaced by the test's asserts
            done["error"] = e
        done["at"] = time.monotonic()

    t = threading.Thread(target=run)
    t.start()
    done["thread"] = t
    return done


@pytest.mark.parametrize("n_parts,part", [(1, 0), (3, 0), (3, 1), (3, 2)],
                         ids=["one", "three-p0", "three-p1", "three-p2"])
@pytest.mark.parametrize("batched", [False, True], ids=["poll", "poll_batch"])
def test_waiting_poll_wakes_on_an_append(n_parts, part, batched):
    cluster, c = _waiting_consumer(n_parts)
    assert c.assignment == list(range(n_parts))
    poll = c.poll_batch if batched else c.poll
    done = _poll_in_thread(lambda: poll(timeout=1.0))
    time.sleep(0.1)
    sent = time.monotonic()
    cluster.append("t", part, Record(b"x"))
    done["thread"].join(2)
    assert "error" not in done
    assert len(done["out"]) == 1 and done["out"][0].partition == part
    assert done["at"] - sent < 0.05  # the poll step alone would add ~1 ms
    assert c.wakes == {"none": 0, "data": 1, "deadline": 0}


@pytest.mark.parametrize("n_parts", [1, 3])
def test_idle_poll_does_not_spin(n_parts, monkeypatch):
    cluster, c = _waiting_consumer(n_parts)
    reads = []
    real = cluster.read
    monkeypatch.setattr(cluster, "read", lambda *a, **kw: reads.append(a) or real(*a, **kw))
    t0 = time.monotonic()
    assert c.poll(timeout=0.3) == []
    assert time.monotonic() - t0 >= 0.3
    # one pass before the wait and one at its deadline; 2 ms steps made ~150
    assert len(reads) <= 3 * n_parts
    assert c.wakes["deadline"] == 1


@pytest.mark.parametrize("how", ["consumer_close", "topic_close", "failover"])
def test_a_wait_ends_cleanly_on_close_or_failover(how):
    cluster, c = _waiting_consumer(1, nodes=2, replication_factor=2)
    done = _poll_in_thread(lambda: c.poll(timeout=2.0))
    time.sleep(0.1)
    start = time.monotonic()
    if how == "consumer_close":
        c.close()
    elif how == "topic_close":
        cluster.close()
    else:
        cluster.fail_node(cluster.topic("t").leaders[0], blackout=0.1)
        Producer(cluster, "t", serializer="raw").send(b"after")
    done["thread"].join(3)
    assert "error" not in done, done.get("error")
    assert done["at"] - start < 1.0  # well before the 2 s deadline
    want = [b"after"] if how == "failover" else []
    assert [m.value for m in done["out"]] == want


def test_poll_with_no_timeout_never_blocks():
    cluster, c = _waiting_consumer(3)
    t0 = time.monotonic()
    assert c.poll(timeout=0) == []
    assert c.poll_batch(timeout=0) == []
    assert time.monotonic() - t0 < 0.05
    cluster.append("t", 1, Record(b"x"))
    assert [m.value for m in c.poll(timeout=0)] == [b"x"]
    assert c.wakes == {"none": 1, "data": 0, "deadline": 2}


def test_poll_span_says_how_the_wait_ended(monkeypatch):
    import repro.broker.consumer as consumer_mod

    spans = _SpanLog()
    monkeypatch.setattr(consumer_mod, "span", spans)
    cluster, c = _waiting_consumer(1)
    assert c.poll(timeout=0.05) == []
    done = _poll_in_thread(lambda: c.poll(timeout=1.0))
    time.sleep(0.1)
    cluster.append("t", 0, Record(b"x"))
    done["thread"].join(2)
    cluster.append("t", 0, Record(b"y"))
    assert [m.value for m in c.poll(timeout=1.0)] == [b"y"]
    assert spans.ended == [
        ("consumer.poll", {"records": 0, "woke": "deadline"}),
        ("consumer.poll", {"records": 1, "woke": "data"}),
        ("consumer.poll", {"records": 1, "woke": "none"}),
    ]


def test_an_append_between_the_read_pass_and_the_wait_is_not_missed(monkeypatch):
    """The append lands after the pass read nothing and before the wait
    begins: the wait must see the sequence moved and re-read at once."""
    cluster, c = _waiting_consumer(1)
    real = cluster.read

    def read_then_append(*a, **kw):
        recs = real(*a, **kw)
        if not recs and cluster.topic("t").partitions[0].high_watermark == 0:
            cluster.append("t", 0, Record(b"late"))
        return recs

    monkeypatch.setattr(cluster, "read", read_then_append)
    t0 = time.monotonic()
    assert [m.value for m in c.poll(timeout=2.0)] == [b"late"]
    assert time.monotonic() - t0 < 0.5


def test_concurrent_appends_all_come_out_once_in_order():
    """More appending threads than cores, with a short switch interval:
    every record comes out once and in partition order, and no poll waits
    out its deadline while appends keep coming."""
    import os
    import sys

    n_threads = (os.cpu_count() or 4) + 2
    per_thread = 200
    cluster, c = _waiting_consumer(3)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def produce(i):
            for k in range(per_thread):
                cluster.append("t", (i + k) % 3, Record(b"%d:%d" % (i, k)))

        threads = [threading.Thread(target=produce, args=(i,)) for i in range(n_threads)]
        for t in threads:
            t.start()
        got = []
        deadline = time.monotonic() + 30
        while len(got) < n_threads * per_thread and time.monotonic() < deadline:
            got.extend(c.poll(max_records=64, timeout=2.0))
        for t in threads:
            t.join(5)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert len(got) == n_threads * per_thread
    assert len({m.value for m in got}) == len(got)
    for p in range(3):
        offsets = [m.offset for m in got if m.partition == p]
        assert offsets == list(range(len(offsets)))
    assert c.wakes["deadline"] == 0


if st is not None:

    @given(st.lists(st.integers(0, 255), min_size=0, max_size=200))
    @settings(max_examples=50, deadline=None)
    def test_msg_serde_roundtrip(xs):
        data = {"xs": bytes(xs), "n": len(xs)}
        assert decode_msg(encode_msg(data)) == data
        assert decode_msg(encode_msg(data, compress=True)) == data

    @given(
        st.integers(1, 50),
        st.integers(1, 8),
        st.sampled_from([np.float32, np.float64, np.int32, np.uint8]),
        st.booleans(),
    )
    @settings(max_examples=50, deadline=None)
    def test_array_serde_roundtrip(n, d, dtype, compress):
        arr = (np.random.default_rng(0).normal(size=(n, d)) * 100).astype(dtype)
        out = decode_array(encode_array(arr, compress=compress))
        np.testing.assert_array_equal(arr, out)
        assert out.dtype == dtype

    @given(st.lists(st.binary(min_size=1, max_size=32), min_size=1, max_size=64),
           st.integers(1, 5))
    @settings(max_examples=30, deadline=None)
    def test_keyed_routing_is_stable(keys, n_parts):
        """Records with equal keys always land in the same partition."""
        cluster = BrokerCluster(1)
        cluster.create_topic("t", n_parts)
        prod = Producer(cluster, "t", serializer="raw")
        placement = {}
        for k in keys:
            prod.send(b"v", key=k)
        for p in range(n_parts):
            for r in cluster.topic("t").partitions[p].read(0, 1000):
                assert placement.setdefault(r.key, p) == p
