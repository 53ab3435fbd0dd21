"""Readings the benchmark's constants are set from, on the chip, in one
process (each run still builds its own pipeline and warms up).

    # the knee: one window at each offered rate (no reference check)
    python3 benchmarks/chip/calibrate.py --workload aps-gridrec.steady \
        --rates 4 5 6 7 --seconds 20 --seed 7
    # the correctness limits: the program's and the control's readings
    python3 benchmarks/chip/calibrate.py --workload aps-gridrec.steady \
        --seeds 11 12 13 --seconds 10

Each run prints one JSON line: the run's notes, its checks and, with
``--seeds``, the same checks with the kind's lower-precision control in the
program's place. A run that raises prints its error and the next one goes
on. Not run by the benchmark's own runs.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from benchmarks.chip.harness import load_cell, notes, run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="*", default=())
    ap.add_argument("--seeds", type=int, nargs="*", default=())
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    runs = [(args.seed, r) for r in args.rates] + [(s, None) for s in args.seeds]
    t_process = T_PROCESS
    for seed, rate in runs:
        if rate is not None:
            cell.traffic = dict(cell.traffic, rate_msgs_s=rate)
        t_start = time.monotonic()
        try:
            out = run_cell(cell, seed, args.seconds, False, t_process=t_process,
                           control=rate is None, check=rate is None)
        except Exception:
            print(json.dumps({"seed": seed, "rate": rate,
                              "error": traceback.format_exc()[-2000:]}), flush=True)
            continue
        finally:
            t_process = time.monotonic()
        line = notes(out)["notes"]
        line.update(rate=cell.traffic["rate_msgs_s"], run_s=time.monotonic() - t_start,
                    correct=out.correct, checks=out.checks,
                    control=out.control_checks, memory_peak_bytes=out.memory_peak_bytes)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
