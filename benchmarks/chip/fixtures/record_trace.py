"""Record the small profiler trace that tests/bench_chip reduces.

    python3 benchmarks/chip/fixtures/record_trace.py   # on a TPU

Three batches through a small gridrec processor (one frame, a stack of
two, one frame) and two through a KMeans processor, each inside the
harness's own spans (``bench.process`` with its batch index, then
``bench.watch`` around the wait for its result), with a 30 ms pause after
each so the chip shows idle gaps the host spends in no span. Writes
``small.xplane.pb`` beside this file.
"""
import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[0] = str(HERE.parents[2])
sys.path.insert(1, str(HERE.parents[2] / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from repro.broker.consumer import Message  # noqa: E402
from repro.miniapps.masa import ReconstructionApp, StreamingKMeans  # noqa: E402


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    rng = np.random.default_rng(0)
    rec = ReconstructionApp("gridrec", n=128, use_kernel=True)
    km = StreamingKMeans(10, 3, use_kernel=True, seed=1)
    frames = [Message(0, i, 0.0, rng.normal(size=(32, 128)).astype(np.float32))
              for i in range(2)]
    pts = [Message(0, 0, 0.0, rng.normal(size=(5000, 3)))]
    batches = [(rec, frames[:1]), (rec, frames), (km, pts), (rec, frames[:1]), (km, pts)]
    for app, msgs in batches:  # compile outside the trace
        jax.block_until_ready(app.process(None, msgs))
        app.sync()
    out = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    for i, (app, msgs) in enumerate(batches):
        with TraceAnnotation("bench.process", batch=i):
            r = app.process(None, msgs)
        with TraceAnnotation("bench.watch"):
            jax.block_until_ready(r)
        app.sync()
        time.sleep(0.03)
    jax.profiler.stop_trace()
    (src,) = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
    shutil.copy(src, HERE / "small.xplane.pb")
    shutil.rmtree(out)
    print(f"wrote {HERE / 'small.xplane.pb'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
