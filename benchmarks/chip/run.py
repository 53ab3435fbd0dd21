"""The chip benchmark's one command.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the accelerator this process finds:
builds the cell's pipeline through the normal path, warms up its shapes,
offers its open-loop traffic for ``--seconds``, drains, compares the
results with the plain reference, and prints as its last stdout line one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
end-to-end metrics, or with ``--trace 1`` the per-layer ones), ``device``,
with ``--trace 1`` a ``breakdown``, and last ``checks``, each number
compared beside its limit. The checks also end standard error. Without a
TPU, with fewer chips than the cell asks for, or with a file of the cell
missing, it prints no result and exits non-zero.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
# the checkout root, not this directory, heads the path: a module here
# must not shadow one of the standard library
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from benchmarks.chip.harness import (  # noqa: E402
    BenchError,
    check_lines,
    load_cell,
    notes,
    result_line,
    run_cell,
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        if not (ROOT / "src" / "repro").is_dir():
            raise BenchError("the system under test (src/repro) is not in this checkout")
        cell = load_cell(args.workload)
        outcome = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_process=T_PROCESS)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    line = result_line(cell, outcome, bool(args.trace))
    print(json.dumps(notes(outcome)), flush=True)
    for text in check_lines(outcome.checks):
        print(text, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
