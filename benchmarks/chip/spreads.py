"""Spread of each metric over sets of runs, the numbers a bound is set from.

    python3 benchmarks/chip/spreads.py A_*.out -- B_*.out

Each file holds one run's standard output; its last line is the result.
For each metric it prints each set's median and spread (the distance
between the first and third quartile of ``statistics.quantiles(values,
n=4)``, as a share of the median) and five times the wider spread.
"""
import json
import statistics
import sys
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[2])

from benchmarks.chip.stats import spread  # noqa: E402


def read_set(paths: list[str]) -> dict[str, list[float]]:
    out: dict[str, list[float]] = {}
    for p in paths:
        lines = Path(p).read_text().strip().splitlines()
        if not lines:
            continue
        line = json.loads(lines[-1])
        for name, m in line["metrics"].items():
            out.setdefault(name, []).append(m["value"])
    return out


def main(argv: list[str]) -> int:
    cut = argv.index("--") if "--" in argv else len(argv)
    sets = [read_set(argv[:cut]), read_set(argv[cut + 1:])]
    for name in sorted(set(sets[0]) | set(sets[1])):
        row = {}
        for i, s in enumerate(sets):
            vals = s.get(name, [])
            if len(vals) >= 2:
                row[f"set{i + 1}"] = {"n": len(vals), "median": statistics.median(vals),
                                     "spread": spread(vals)}
        widest = max((r["spread"] for r in row.values()), default=None)
        print(json.dumps({"metric": name, **row,
                          "five_times_widest": None if widest is None else 5 * widest}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
