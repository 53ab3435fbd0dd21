"""Shared by the benchmark's CPU tests: cells held out of BENCHMARK.json
(their files stay under benchmarks/chip) load through a copy of it that
names them again, so their files keep being tested."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip.harness import load_cell  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
#: cells out of BENCHMARK.json until their limits have chip readings
#: (PERF.md, Open questions); named <config>.<traffic mix>
HELD = ["mass-kmeans-shm.steady"]
CELLS = [w["name"] for w in BENCH["workloads"]] + HELD


def bench_naming(cell: str) -> dict:
    """BENCHMARK.json, with ``cell`` added back if it is held."""
    bench = json.loads(json.dumps(BENCH))
    if cell in {w["name"] for w in bench["workloads"]}:
        return bench
    config = cell.split(".")[0]
    bench["configs"].append({"name": config, "source": "held", "reduced": [], "why": "held",
                             "file": f"benchmarks/chip/configs/{config}.json"})
    bench["workloads"].append({"name": cell, "config": config, "traffic": cell, "chips": 1,
                               "why": "held"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    return bench


@pytest.fixture
def cell_loader(tmp_path):
    def load(cell: str):
        path = tmp_path / "BENCHMARK.json"
        path.write_text(json.dumps(bench_naming(cell)))
        return load_cell(cell, path)
    return load
