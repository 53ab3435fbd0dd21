"""Record the profiler trace of the program's own spans that tests/bench_chip
reduces, and the truth its attribution is checked against.

    python3 benchmarks/chip/fixtures/record_spans.py   # on a TPU

A small micro-batch pipeline through the normal path (one broker node, shm
data plane, one partition, a gridrec stage at n = 128 with the kernels and
a double buffer of depth 2) takes bursts of 1 to 8 frames of 360 x 128,
sent faster than the chip reconstructs them, so batches queue on the
device and the engine blocks in ``app.wait``. The processor is wrapped as
the harness wraps it (``bench.process`` with the batch's index) and also
records each call of its two programs. The profiler, with the harness's
options, starts while batches are in flight and stops once the pipeline
is idle. Writes beside this file ``small_spans.xplane.pb`` and
``small_spans.truth.json``: the traced window's length, and per program
call, in order, the batch's index, the engine's batch id, the program, the
frames in the call and its padded depth, and ``traced``: whether the call
began after the profiler started (true), ended before it began starting
(false) or overlapped that (null).
"""
import glob
import json
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[0] = str(HERE.parents[2])
sys.path.insert(1, str(HERE.parents[2] / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from repro.broker.consumer import Message  # noqa: E402
from repro.broker.producer import Producer  # noqa: E402
from repro.pipeline import Pipeline, register_processor  # noqa: E402
from repro.pipeline.registry import make_processor  # noqa: E402

ANGLES, DET, N = 360, 128, 128
BURSTS = [1, 3, 8, 2, 5, 1, 8, 4, 2, 6, 1, 7, 3, 8, 2, 1, 5, 8, 2, 4]
#: bursts sent before the profiler starts
BEFORE = 6


class Recorded:
    """The stage's processor, wrapped: ``bench.process`` around each call,
    and a record of each call of its programs."""

    def __init__(self, inner):
        self.inner = inner
        self.calls: list[dict] = []
        self.batches = 0
        self.stream = None
        self._batch = (-1, 0, 0)
        frame_name, stack_name = inner._program_names
        inner._rec = self._program(inner._rec, frame_name)
        inner._rec_batch = self._program(inner._rec_batch, stack_name)

    def _program(self, fn, name):
        def call(x, angles):
            index, engine_batch, frames = self._batch
            start = time.monotonic()
            out = fn(x, angles)
            self.calls.append({"batch": index, "engine_batch": engine_batch,
                               "program": name, "frames": frames,
                               "depth": int(x.shape[0]) if x.ndim == 3 else 1,
                               "start": start, "end": time.monotonic()})
            return out
        return call

    def process(self, state, msgs):
        index = self.batches
        self.batches += 1
        self._batch = (index, self.stream._batch_id + 1, len(msgs))
        with TraceAnnotation("bench.process", batch=index):
            return self.inner.process(state, msgs)

    def sync(self):
        self.inner.sync()


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_spans: needs a TPU", file=sys.stderr)
        return 1
    made = []

    def factory(metrics=None, **opts):
        made.append(Recorded(make_processor("gridrec", opts, metrics=metrics)))
        return made[0]

    register_processor("fixture.gridrec", factory)
    spec = (Pipeline.named("fixture-spans")
            .broker(nodes=1, transport="shm",
                    transport_options={"slot_bytes": ANGLES * DET * 4 + 4096, "n_slots": 16})
            .topic("frames", partitions=1)
            .stage("stage", topic="frames", processor="fixture.gridrec", transport="shm",
                   batch_interval=0.004, max_batch_records=8, n=N, use_kernel=True)
            .build())
    run = spec.run().start()
    proc = made[0]
    proc.stream = run.stream("stage")
    rng = np.random.default_rng(0)
    frames = [rng.normal(size=(ANGLES, DET)).astype(np.float32) for _ in range(8)]
    app = proc.inner
    for depth in (1, 2, 3, 5):  # every program and stack bucket, outside the trace
        app.process(None, [Message(0, i, 0.0, f) for i, f in enumerate(frames[:depth])])
    app.sync()
    proc.calls.clear()

    producer = Producer(run.cluster, "frames")
    started = threading.Event()
    marks = {}

    def send():
        for i, size in enumerate(BURSTS):
            if i == BEFORE:
                started.set()
            for f in frames[:size]:
                producer.send_batch([f])
            time.sleep(0.004)

    sender = threading.Thread(target=send)
    sender.start()
    started.wait()
    out = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    marks["before"] = time.monotonic()
    jax.profiler.start_trace(out, profiler_options=opts)
    marks["after"] = time.monotonic()
    sender.join()
    total = sum(BURSTS)
    deadline = time.monotonic() + 30
    while proc.stream.stats.records < total and time.monotonic() < deadline:
        time.sleep(0.01)
    app.sync()
    time.sleep(0.05)
    # the trace's clock starts inside start_trace and stops inside stop_trace
    window_s = time.monotonic() - marks["before"]
    jax.profiler.stop_trace()
    run.stop()
    (src,) = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
    shutil.copy(src, HERE / "small_spans.xplane.pb")
    shutil.rmtree(out)
    for c in proc.calls:
        c["traced"] = (True if c["start"] > marks["after"]
                       else False if c["end"] < marks["before"] else None)
        del c["start"], c["end"]
    (HERE / "small_spans.truth.json").write_text(json.dumps({"window_s": window_s, "calls": proc.calls}, indent=1))
    print(f"wrote {HERE / 'small_spans.xplane.pb'}: {len(proc.calls)} program calls, "
          f"{sum(c['traced'] is True for c in proc.calls)} traced")
    return 0


if __name__ == "__main__":
    sys.exit(main())
