"""`repro-pipeline` — run and validate declarative pipeline specs.

    repro-pipeline validate spec.json [--import mymodule]
    repro-pipeline run spec.json --devices 8 [--duration 10] [--share 2]

(or ``python -m repro.pipeline ...`` without installing the console script.)

``validate`` rehydrates the builder from the JSON spec and prints the
builder's **full** error list — the same checks ``Pipeline.build()`` runs,
so a spec that validates here will provision. ``--import`` loads modules
first so custom processors/sources/sinks registered at import time are
known to the validator (and the runner).
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time

from repro.pipeline.builder import Pipeline
from repro.pipeline.spec import PipelineSpec
from repro.utils import enable_compile_cache


def _load_spec(path: str) -> PipelineSpec:
    with open(path) as f:
        return PipelineSpec.from_dict(json.load(f))


def _import_modules(mods: list[str]) -> None:
    for m in mods:
        importlib.import_module(m)


def _validate(spec: PipelineSpec) -> list[str]:
    return Pipeline.from_spec(spec).validate()


def cmd_validate(args: argparse.Namespace) -> int:
    _import_modules(args.imports)
    spec = _load_spec(args.spec)
    errors = _validate(spec)
    if errors:
        print(f"invalid pipeline {spec.name!r} ({len(errors)} problem"
              f"{'s' if len(errors) != 1 else ''}):", file=sys.stderr)
        for e in errors:
            print(f"  - {e}", file=sys.stderr)
        return 1
    n_el = sum(1 for s in spec.stages if s.elastic is not None)
    print(f"{args.spec}: pipeline {spec.name!r} OK "
          f"({len(spec.broker.topics)} topics, {len(spec.sources)} sources, "
          f"{len(spec.stages)} stages [{n_el} elastic], "
          f"{len(spec.sinks)} sinks"
          f"{', elastic broker' if spec.broker.elastic else ''})")
    return 0


def cmd_run(args: argparse.Namespace) -> int:
    enable_compile_cache()
    _import_modules(args.imports)
    spec = _load_spec(args.spec)
    errors = _validate(spec)
    if errors:
        print(f"invalid pipeline {spec.name!r}:", file=sys.stderr)
        for e in errors:
            print(f"  - {e}", file=sys.stderr)
        return 1
    finite = all(s.total_messages is not None for s in spec.sources) and spec.sources
    with spec.run(devices=args.devices, share=args.share) as run:
        t0 = time.monotonic()
        next_report = args.report_every
        try:
            while True:
                elapsed = time.monotonic() - t0
                if args.duration is not None and elapsed >= args.duration:
                    break
                time.sleep(0.25)  # poll fast, print at --report-every cadence
                lags = {s.name: run.lag(s.name) for s in spec.stages}
                if elapsed >= next_report:
                    next_report += args.report_every
                    devs = {n: c.devices for n, c in run.controllers.items()}
                    print(f"t={elapsed:6.1f}s  lag={lags}"
                          + (f"  devices={devs}" if devs else ""))
                # early exit only when finite sources have actually drained
                # their quotas AND consumers caught up — lag alone reads 0
                # whenever consumers merely keep pace with production
                if (finite and run.sources_finished
                        and all(v == 0 for v in lags.values())):
                    break
        except KeyboardInterrupt:
            pass
        for s in spec.stages:
            st = run.stream(s.name).stats
            records = getattr(st, "records", 0)
            print(f"stage {s.name!r}: {records} records")
    if run.errors:
        for e in run.errors:
            print(f"teardown error: {e!r}", file=sys.stderr)
        return 1
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro-pipeline",
        description="Run/validate declarative streaming-pipeline specs "
                    "(repro.pipeline; see docs/pipeline.md)",
    )
    sub = ap.add_subparsers(dest="cmd", required=True)

    val = sub.add_parser("validate", help="check a spec, print every problem")
    val.add_argument("spec", help="path to a PipelineSpec JSON file")
    val.add_argument("--import", dest="imports", action="append", default=[],
                     metavar="MODULE",
                     help="import MODULE first (registers custom "
                          "processors/sources/sinks); repeatable")
    val.set_defaults(fn=cmd_validate)

    runp = sub.add_parser("run", help="provision and run a spec")
    runp.add_argument("spec", help="path to a PipelineSpec JSON file")
    runp.add_argument("--devices", type=int, default=None,
                      help="device-pool size (default: all local devices)")
    runp.add_argument("--duration", type=float, default=10.0,
                      help="seconds to run (finite sources may stop earlier); "
                           "default 10")
    runp.add_argument("--share", type=float, default=None,
                      help="override the spec's pipeline-level fair-share weight")
    runp.add_argument("--report-every", type=float, default=1.0,
                      help="seconds between progress lines")
    runp.add_argument("--import", dest="imports", action="append", default=[],
                      metavar="MODULE", help="import MODULE first; repeatable")
    runp.set_defaults(fn=cmd_run)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
