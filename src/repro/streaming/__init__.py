from repro.streaming.dispatch import (
    AsyncWindow,
    LatencyWindow,
    ShapeBuckets,
    compile_count,
    pad_rows,
)
from repro.streaming.rate_control import PIDRateController
from repro.streaming.windows import (
    SessionWindow,
    SlidingWindow,
    TumblingWindow,
    WatermarkTracker,
)

__all__ = [
    "AsyncWindow",
    "LatencyWindow",
    "PIDRateController",
    "SessionWindow",
    "ShapeBuckets",
    "SlidingWindow",
    "TumblingWindow",
    "WatermarkTracker",
    "compile_count",
    "pad_rows",
]
