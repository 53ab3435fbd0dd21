"""The chip benchmark's files: BENCHMARK.json keeps to its schema, every
cell finds its configuration, traffic, kind and metric files, and a cell
with a file missing, or a run without a TPU, is refused before any result."""
import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench_cells import CELLS as WITH_HELD, cell_loader  # noqa: E402,F401  (a fixture)
from benchmarks.chip.harness import BenchError, load_cell  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p) and ".." not in p
        assert (ROOT / p).is_dir()
    assert all(isinstance(w, str) and 0 < len(w) <= 200 for w in BENCH["command"])
    assert (ROOT / BENCH["command"][1]).is_file()


def test_names_units_and_entry_keys():
    names = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["name"] not in names
        names.add(m["name"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("cell", WITH_HELD)
def test_each_cell_loads_its_files(cell, cell_loader):
    c = cell_loader(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for name, reader in c.readers.items():
        assert callable(reader.read), name
    for key in ("make_pool", "warmup", "Collector", "compare", "control", "work",
                "seeded_options"):
        assert hasattr(c.kind, key), key
    assert c.traffic["rate_msgs_s"] > 0


@pytest.mark.parametrize("config", sorted(p.stem for p in (ROOT / "benchmarks/chip/configs").glob("*.json")))
def test_config_states_what_it_runs(config):
    """Every configuration file, held ones too; those in BENCHMARK.json
    agree with their entry."""
    entry = next((c for c in BENCH["configs"] if c["name"] == config), None)
    cfg = json.loads((ROOT / "benchmarks/chip/configs" / f"{config}.json").read_text())
    assert cfg["name"] == config
    assert entry is None or (cfg["reduced"] == entry["reduced"]
                             and entry["file"] == f"benchmarks/chip/configs/{config}.json")
    assert {"delivery", "precision"} <= set(cfg["guarantees"])
    assert cfg["stage"]["options"]["use_kernel"] is True
    assert len(cfg["source"]) <= 200 and cfg["assumed"]


@pytest.mark.parametrize("missing", ["traffic", "kind", "metric", "config", "workload"])
def test_a_missing_file_is_refused(tmp_path, missing):
    bench = json.loads(json.dumps(BENCH))
    cell = bench["workloads"][0]
    if missing == "traffic":
        cell["traffic"] = "no-such-mix"
    elif missing == "kind":
        cfg = json.loads((ROOT / bench["configs"][0]["file"]).read_text())
        cfg["kind"] = "no_such_kind"
        path = tmp_path / "cfg.json"
        path.write_text(json.dumps(cfg))
        bench["configs"][0]["file"] = str(path)
    elif missing == "metric":
        bench["end_to_end"].append({"name": "no_such_metric", "unit": "s", "better": "lower",
                                    "bound": 0.1, "source": "host_clock"})
    elif missing == "config":
        bench["configs"][0]["file"] = "benchmarks/chip/configs/no-such-config.json"
    else:
        cell["name"] = "renamed"
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    with pytest.raises(BenchError, match="missing file|no workload"):
        load_cell(BENCH["workloads"][0]["name"], path)


def _run(cwd: Path, env_extra: dict) -> subprocess.CompletedProcess:
    import os

    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    res = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "no TPU" in res.stderr


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths
    has no system under test: no result, a non-zero exit."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    res = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert res.returncode != 0
    assert res.stdout.strip() == ""
