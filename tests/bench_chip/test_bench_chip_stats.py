"""Arithmetic of the chip benchmark on synthetic stamps: percentiles,
spread, slopes, the arrival schedule and the host-clock metric readers."""
import statistics
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import stats, traffic  # noqa: E402
from benchmarks.chip.harness import Batch, RunRecord, delivery_checks, load_module  # noqa: E402

METRICS = ROOT / "benchmarks" / "chip" / "metrics"


def reader(name):
    return load_module(METRICS / f"{name}.py", "metric").read


def record(due, done, batches, *, lag=(), engine=(), seconds=10.0):
    n = len(due)
    return RunRecord(
        cell="synthetic", seed=1, seconds=seconds, config={}, setup_s=12.5, t0=100.0,
        due=np.asarray(due, float), sent=np.asarray(due, float), done=np.asarray(done, float),
        order=np.zeros(n, int), batches=batches, lag=list(lag), engine=list(engine),
        work={}, peaks=None, compiles_in_window=0, partition=np.zeros(n, int))


def test_percentile_and_spread():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == pytest.approx(50.5)
    assert stats.percentile(v, 95) == pytest.approx(95.05)
    assert stats.percentile([np.nan, 3.0], 50) == 3.0
    assert stats.percentile([], 50) is None
    vals = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, q2, q3 = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx((q3 - q1) / q2)


def test_slope_and_union():
    t = np.arange(20) * 0.1
    assert stats.slope(t, 3.0 * t + 7.0) == pytest.approx(3.0)
    assert stats.slope([1.0], [2.0]) is None
    assert stats.union_length([(0, 1), (0.5, 2), (3, 4), (3.5, 3.6)]) == pytest.approx(3.0)


def test_latency_readers_on_hand_counted_stamps():
    # ten messages due at 100.0 .. 100.9; batches of two start 10 ms after
    # the second is due and are ready 40 ms after they start
    due = 100.0 + np.arange(10) * 0.1
    batches, done = [], np.zeros(10)
    for k in range(5):
        start = due[2 * k + 1] + 0.010
        b = Batch(k, [2 * k, 2 * k + 1], start, start + 0.001, done=start + 0.040)
        done[2 * k: 2 * k + 2] = b.done
        batches.append(b)
    run = record(due, done, batches)
    lat_ms = (done - due) * 1e3  # 150 ms for the first of a pair, 50 for the second
    assert reader("result_latency_p50_ms")(run) == pytest.approx(100.0)
    assert reader("result_latency_p95_ms")(run) == pytest.approx(np.percentile(lat_ms, 95))
    assert reader("queue_wait_p95_ms")(run) == pytest.approx(110.0)
    assert reader("service_p50_ms")(run) == pytest.approx(40.0)
    assert reader("throughput_msgs_s")(run) == pytest.approx(1.0)
    assert reader("setup_s")(run) == 12.5


def test_a_message_without_a_result_is_failed_not_fast():
    due = [100.0, 100.1, 100.2]
    b = Batch(0, [0, 1], 100.15, 100.16, done=100.2)
    run = record(due, [100.2, 100.2, np.nan], [b])
    assert reader("result_latency_p50_ms")(run) == pytest.approx(150.0)
    assert delivery_checks(run, np.array([1, 1, 0]))["failed_msgs"]["value"] == 1
    assert delivery_checks(run, np.array([1, 2, 1]))["failed_msgs"]["value"] == 2


def test_reordered_messages_fail():
    due = [100.0, 100.1]
    run = record(due, [100.3, 100.3], [Batch(0, [1], 0, 0, done=100.3),
                                       Batch(1, [0], 0, 0, done=100.3)])
    assert delivery_checks(run, np.array([1, 1]))["failed_msgs"]["value"] == 1


def test_engine_and_backlog_readers():
    lag = [(100.0 + 0.1 * i, 5 + 2 * i) for i in range(30)]  # 20 msgs/s growth
    run = record([100.0], [100.1], [], lag=lag, engine=[(4, 0.002), (6, 0.008)])
    assert reader("backlog_growth_msgs_s")(run) == pytest.approx(20.0)
    assert reader("host_ms_per_msg")(run) == pytest.approx(1.0)
    assert reader("device_idle_share")(run) is None  # no trace, nothing to read
    assert reader("tomo_bp_roofline")(run) is None
    assert reader("gridrec_mfu")(run) is None


@pytest.mark.parametrize("seed", [0, 2**31 + 17, 12345678901])
def test_arrivals_same_work_other_order(seed):
    """Every seed offers the same arrivals; the seed orders the payloads."""
    mix = {"rate_msgs_s": 50.0}
    due = traffic.due_offsets(mix, 10.0)
    assert due.min() >= 0 and due.max() < 10.0 and np.all(np.diff(due) > 0)
    assert np.array_equal(due, traffic.due_offsets(mix, 10.0))
    order = traffic.payload_order(len(due), 16, seed)
    assert order.min() >= 0 and order.max() < 16
    assert np.array_equal(order, traffic.payload_order(len(due), 16, seed))
    assert not np.array_equal(order, traffic.payload_order(len(due), 16, seed + 1))


def test_a_schedule_seed_fixes_the_arrivals():
    """The schedule comes from the benchmark's own stream: a shorter window
    offers a prefix of a longer one's arrivals."""
    mix = {"rate_msgs_s": 5.2}
    due = traffic.due_offsets(mix, 40.0)
    longer = traffic.due_offsets(mix, 51.0)
    assert len(due) == 211  # the realisation of this stream at 5.2 a second
    assert np.array_equal(longer[: len(due)], due) and longer[len(due)] >= 40.0


def test_arrivals_are_poisson_at_the_rate():
    """Exponential gaps: mean 1 / rate, coefficient of variation near 1,
    and counts in one-second bins with variance near their mean."""
    due = traffic.due_offsets({"rate_msgs_s": 2000.0}, 40.0)
    gaps = np.diff(due)
    assert len(due) / 40.0 == pytest.approx(2000.0, rel=0.02)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, rel=0.02)
    counts = np.bincount(due.astype(int), minlength=40)
    assert counts.var() / counts.mean() == pytest.approx(1.0, abs=0.6)
