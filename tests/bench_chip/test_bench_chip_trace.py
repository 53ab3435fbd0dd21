"""The trace reduction on a small trace recorded on one v5e
(``benchmarks/chip/fixtures/record_trace.py``): three gridrec batches (one
frame, a stack of two, one frame) and two KMeans batches, each in the
harness's spans, 30 ms apart. The expected values were read off the
trace's events by hand."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip.harness import load_module  # noqa: E402
from benchmarks.chip.trace import NO_SPAN, op_name, summarize  # noqa: E402

FIXTURE = ROOT / "benchmarks" / "chip" / "fixtures" / "small.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return summarize(FIXTURE, window_s=0.2)


def test_op_name():
    assert op_name("%backproject_pallas.1 = f32[1472,1536]{1,0} custom-call(..)") \
        == "backproject_pallas.1"
    assert op_name("fusion.3") == "fusion.3"


def test_busy_time_is_the_union_of_ops(summary):
    # 88 ops in 72 disjoint stretches, 696,733 ns in all
    assert len(summary.ops) == 1 and len(summary.ops[0]) == 88
    assert summary.busy_s == pytest.approx(696_733e-9, rel=1e-9)
    idle = load_module(ROOT / "benchmarks/chip/metrics/device_idle_share.py", "metric")

    class Run:
        trace = summary
    assert idle.read(Run) == pytest.approx(100 * (1 - 696_733e-9 / 0.2))


def test_kernel_time_by_batch(summary):
    bp = summary.kernel_events(r"backproject_pallas")
    assert [b for _, b in bp] == [0, 1, 3]
    assert [round(dt * 1e9) for dt, _ in bp] == [83_580, 166_875, 83_581]
    km = summary.kernel_events(r"assign_pallas")
    assert [b for _, b in km] == [2, 4]
    assert all(round(dt * 1e9) == 31_883 for dt, _ in km)


def test_gaps_and_breakdown(summary):
    gaps = sorted(summary.gaps, key=lambda g: g[0] - g[1])
    # before the first op (45.663 ms), then the four 30 ms pauses
    lengths = [round((e - s) * 1e9) for s, e, _ in gaps[:5]]
    assert lengths == [45_663_031, 33_367_383, 32_777_021, 32_587_969, 32_558_080]
    assert all(label == NO_SPAN for *_, label in gaps[:5])
    # the host was still in batch 1's process span when this one opened
    (s, e, label), = [g for g in gaps if round((g[1] - g[0]) * 1e9) == 854_888]
    assert label == "bench.process"
    bd = summary.breakdown()
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10
    assert bd["device_ops"][0][0] == "backproject_pallas.1"
    assert bd["device_ops"][0][1] == pytest.approx(83_580e-9 + 83_581e-9)
