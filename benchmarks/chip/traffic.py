"""The one open-loop generator: due times from a traffic file, payloads
from a seeded pool, sends on schedule from a thread of its own.

A traffic file (``traffic/<mix>.json``) holds parameters only:

* ``rate_msgs_s``: the mean offered rate, messages a second. Messages are
  due at the arrivals of a Poisson process at that rate: exponential gaps
  drawn from one stream of the benchmark's own (``SCHEDULE_SEED``), not
  from the run's seed. Every run of a cell thus offers the same arrivals,
  and the run's seed picks which payload each one carries. A shorter
  window offers a prefix of a longer one's arrivals.

The generator never waits on results. Each message carries the time it
was due as its record timestamp, and the generator records when it was
actually sent, so lateness and latency are taken from the due time.
"""
from __future__ import annotations

import threading
import time
from contextlib import nullcontext

import numpy as np

#: the arrival schedule's own stream: the same arrivals in every run
SCHEDULE_SEED = 20260417
#: gaps drawn at a time; the schedule does not depend on it
_CHUNK = 4096


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one use (``stream``) of a run's seed. Seeds may
    exceed 32 bits; negative ones are folded onto non-negative entropy."""
    return np.random.default_rng([abs(int(seed)), int(seed < 0), int(stream)])


def check_traffic(traffic: dict) -> None:
    if "rate_msgs_s" not in traffic:
        raise ValueError("traffic file lacks rate_msgs_s")
    if traffic["rate_msgs_s"] <= 0:
        raise ValueError("rate_msgs_s must be positive")


def due_offsets(traffic: dict, seconds: float) -> np.ndarray:
    """Seconds from the window's start at which each message is due,
    ascending, all inside [0, seconds)."""
    check_traffic(traffic)
    rng = np.random.default_rng(SCHEDULE_SEED)
    mean_gap = 1.0 / float(traffic["rate_msgs_s"])
    chunks, t = [], 0.0
    while t < seconds:
        due = t + np.cumsum(rng.exponential(mean_gap, _CHUNK))
        chunks.append(due)
        t = float(due[-1])
    due = np.concatenate(chunks)
    return due[due < seconds]


def payload_order(n_messages: int, pool_size: int, seed: int) -> np.ndarray:
    """Which pool entry each message carries, drawn from the seed."""
    return rng_for(seed, 2).integers(0, pool_size, size=n_messages)


class OpenLoopGenerator:
    """Sends ``pool[order[i]]`` at ``due[i]`` (monotonic seconds) through
    ``producer.send_batch``, stamped with its due time; records when each
    was sent."""

    def __init__(self, producer, pool: list, order: np.ndarray, due: np.ndarray,
                 *, wall_minus_mono: float, annotate=None):
        self.producer = producer
        self.pool = pool
        self.order = order
        self.due = due
        self.sent = np.full(len(due), np.nan)
        self.error: BaseException | None = None
        self._annotate = annotate
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="bench-generator",
                                        daemon=True)
        # record timestamps are wall-clock seconds, like the broker's own
        self._wall_minus_mono = wall_minus_mono

    def start(self) -> "OpenLoopGenerator":
        self._thread.start()
        return self

    def _run(self) -> None:
        try:
            for i, due in enumerate(self.due):
                wait = due - time.monotonic()
                if wait > 0 and self._stop.wait(wait):
                    return
                span = self._annotate("bench.send") if self._annotate else nullcontext()
                with span:
                    self.producer.send_batch(
                        [self.pool[self.order[i]]],
                        timestamps=[float(due + self._wall_minus_mono)])
                self.sent[i] = time.monotonic()
        except BaseException as e:  # surfaced by the harness after the drain
            self.error = e

    def finished(self) -> bool:
        return not self._thread.is_alive()

    def stop(self, timeout: float = 10.0) -> None:
        self._stop.set()
        self._thread.join(timeout)
