"""Streaming hot-path dispatch: shape buckets + async double-buffering.

The micro-batch ``process()`` hot path has two structural costs that dominate
per-message overhead (paper §6.4 / the serverless-HPC characterization
follow-up):

1. **Recompiles** — ``jax.jit`` specializes on input shapes, so every
   distinct batch size from a variable-rate source triggers a fresh XLA
   compile. :class:`ShapeBuckets` quantizes sizes to a small power-of-two
   set; batches are zero-padded up to their bucket and processed with masked
   updates, so steady state runs with at most ``len(buckets)`` compiles.

2. **Dispatch stalls** — an unconditional ``block_until_ready()`` after
   every batch serializes host dispatch against device compute.
   :class:`AsyncWindow` keeps a bounded number of batches in flight
   (double-buffering at ``depth=2``): batch N+1 is dispatched while batch N
   executes, and the host only blocks when the window is full or at an
   explicit ``sync()`` boundary (stats read, checkpoint, elastic rescale —
   see docs/perf.md for the sync contract).

:class:`LatencyWindow` tracks rolling per-batch completion latency and
exposes p50/p99 for the elastic ``MetricsBus``.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable

import jax
import numpy as np

from repro.elastic.metrics import span


def next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


class ShapeBuckets:
    """Quantize variable sizes to a fixed power-of-two bucket set.

    Sizes above ``max_size`` round up to the next multiple of ``max_size``
    (rare giant batches cost one extra compile each instead of unbounded
    bucket growth).
    """

    def __init__(self, min_size: int = 256, max_size: int = 65536):
        self.min_size = next_pow2(min_size)
        self.max_size = max(next_pow2(max_size), self.min_size)
        sizes, s = [], self.min_size
        while s <= self.max_size:
            sizes.append(s)
            s *= 2
        self.sizes: tuple[int, ...] = tuple(sizes)

    def fit(self, n: int) -> int:
        """Smallest bucket that holds ``n`` rows."""
        for s in self.sizes:
            if n <= s:
                return s
        return -(-n // self.max_size) * self.max_size

    def __len__(self) -> int:
        return len(self.sizes)

    def __iter__(self):
        return iter(self.sizes)

    def __repr__(self) -> str:
        return f"ShapeBuckets({list(self.sizes)})"


def pad_rows(arr: np.ndarray, size: int) -> np.ndarray:
    """Zero-pad axis 0 of ``arr`` up to ``size`` rows (host-side, cheap)."""
    if arr.shape[0] >= size:
        return arr
    out = np.zeros((size,) + arr.shape[1:], arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def compile_count(jitted: Callable) -> int:
    """Number of distinct XLA compilations a jitted fn has performed."""
    try:
        return int(jitted._cache_size())
    except Exception:
        return -1


class LatencyWindow:
    """Rolling window of per-batch latencies with cheap quantiles."""

    def __init__(self, maxlen: int = 256):
        self._lat: deque[float] = deque(maxlen=maxlen)
        self.count = 0

    def record(self, dt: float) -> None:
        self._lat.append(dt)
        self.count += 1

    def quantile(self, q: float) -> float:
        if not self._lat:
            return 0.0
        return float(np.quantile(np.asarray(self._lat), q))

    @property
    def p50(self) -> float:
        return self.quantile(0.50)

    @property
    def p99(self) -> float:
        return self.quantile(0.99)

    def __len__(self) -> int:
        return len(self._lat)


class AsyncWindow:
    """Bounded window of in-flight jax computations (double buffering).

    ``push(result, meta)`` enqueues a just-dispatched result. When more than
    ``depth`` results are pending the oldest is blocked on, so the device
    queue stays bounded while newer batches dispatch. Each completed entry is
    returned as ``(result, meta, latency_s)`` — callers fold these into
    their stats. ``depth=0`` degenerates to fully synchronous execution
    (the pre-overhaul behavior, kept for before/after benchmarking).
    """

    def __init__(self, depth: int = 2, latency: LatencyWindow | None = None):
        self.depth = max(int(depth), 0)
        self.latency = latency
        self._pending: deque[tuple[Any, Any, float]] = deque()
        # the engine thread pushes; sync() may come from a rescale/stats
        # thread — serialize drains so both never pop the same entry
        self._lock = threading.Lock()

    def push(self, result: Any, meta: Any = None,
             t0: float | None = None) -> list[tuple[Any, Any, float]]:
        """Enqueue a dispatched result. ``t0`` is the batch's start-of-work
        timestamp (defaults to now): completion latency is measured from it,
        so host-side batch prep counts toward the recorded latency."""
        done = []
        with self._lock:
            self._pending.append((result, meta, time.monotonic() if t0 is None else t0))
            while len(self._pending) > self.depth:
                done.append(self._wait_oldest())
        return done

    def _wait_oldest(self) -> tuple[Any, Any, float]:
        with span("app.wait"):
            result, meta, t0 = self._pending.popleft()
            jax.block_until_ready(result)
        dt = time.monotonic() - t0
        if self.latency is not None:
            self.latency.record(dt)
        return result, meta, dt

    def sync(self) -> list[tuple[Any, Any, float]]:
        """Drain every in-flight batch (the stats/checkpoint/rescale barrier)."""
        done = []
        with self._lock:
            while self._pending:
                done.append(self._wait_oldest())
        return done

    def discard(self) -> int:
        """Drop every pending entry without waiting on or delivering it.
        Crash-injection path: replay re-produces the dropped work, so
        delivering it here would double-count. Returns the count dropped."""
        with self._lock:
            n = len(self._pending)
            self._pending.clear()
            return n

    @property
    def in_flight(self) -> int:
        return len(self._pending)

    @property
    def busy(self) -> bool:
        """Whether a pending result is still computing on the device. Does
        not block. Unlike ``in_flight``, an entry whose result is ready but
        not yet drained (the window drains only past ``depth`` or at
        ``sync()``) does not count."""
        return any(not leaf.is_ready()
                   for result, _, _ in tuple(self._pending)
                   for leaf in jax.tree_util.tree_leaves(result)
                   if isinstance(leaf, jax.Array))
