"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only startup,latency,...]

Prints ``name,us_per_call,derived`` CSV rows (paper Figs. 6-9 analogs +
kernel micro-benchmarks + the roofline summary from dry-run artifacts).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

from repro.utils import enable_compile_cache

SUITES = ("startup", "latency", "producer_throughput", "processing_throughput",
          "elasticity", "predictive", "kernel_bench", "hotpath")


def _roofline_rows() -> list[tuple[str, float, str]]:
    """Summarize the dry-run roofline artifacts if present (see launch/dryrun)."""
    path = os.path.join(os.path.dirname(__file__), "roofline_opt.json")
    if not os.path.exists(path):
        path = os.path.join(os.path.dirname(__file__), "roofline_baseline.json")
    if not os.path.exists(path):
        return [("roofline", 0.0, "missing: run launch.dryrun + launch.roofline first")]
    with open(path) as f:
        rows = json.load(f)
    out = []
    for r in rows:
        ideal = max(r["compute_s"], r["memory_s"], r["collective_s"])
        out.append((
            f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}",
            ideal * 1e6,
            f"bottleneck={r['dominant']};fraction={r['fraction']:.3f}",
        ))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated suite names")
    args = ap.parse_args()
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    failures = 0
    for suite in SUITES:
        if only and suite not in only:
            continue
        try:
            mod = __import__(f"benchmarks.{suite}", fromlist=["run"])
            for name, us, derived in mod.run():
                print(f"{name},{us:.1f},{derived}")
                sys.stdout.flush()
        except Exception:
            failures += 1
            traceback.print_exc()
    if only is None or "roofline" in (only or set()):
        for name, us, derived in _roofline_rows():
            print(f"{name},{us:.1f},{derived}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
