"""Streaming-serving driver: request stream -> broker -> prefill/decode.

The paper's Type-1 pipeline (external instrument -> analysis): requests are
token prompts; the MASA serving app prefills and decodes a fixed budget per
request batch.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
      --requests 8 --gen-tokens 8
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.registry import get_arch
from repro.core import PilotComputeService
from repro.elastic import MetricsBus
from repro.launch import instrumented
from repro.miniapps import LMServeApp, SourceConfig, TokenSource
from repro.scheduler import ResourceRequest
from repro.utils import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8, help="request batches to serve")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-tokens", type=int, default=8)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    bus = MetricsBus()
    svc = PilotComputeService(metrics=bus)
    kafka = svc.submit_pilot({"number_of_nodes": 1, "type": "kafka"})
    cluster = kafka.get_context()
    cluster.create_topic("requests", 2)
    spark = svc.submit_pilot({"number_of_nodes": 1, "type": "spark"})
    ctx = spark.get_context()
    held = len(spark.lease.devices)
    svc.get_arbiter(bus).submit(ResourceRequest(
        "launch/serve", min_devices=held, max_devices=held, target=held,
        current_fn=lambda: len(spark.lease.devices)))

    app = LMServeApp(cfg, prompt_len=args.prompt_len, gen_tokens=args.gen_tokens, batch=args.batch)
    params = app.model.init(jax.random.key(0))

    source = TokenSource(
        cluster,
        SourceConfig("requests", total_messages=args.requests),
        vocab_size=cfg.vocab_size,
        seq_len=args.prompt_len,
        seqs_per_msg=args.batch,
    ).start()

    stream = ctx.stream(
        cluster, "requests", group="server",
        process_fn=instrumented(app, bus, "serve"), state=params,
        batch_interval=0.1, max_batch_records=1,
        metrics=bus, metrics_label="serve",
    ).start()
    t0 = time.time()
    stream.await_batches(args.requests, timeout=3600)
    stream.stop()
    source.stop()
    dt = time.time() - t0
    print(
        f"[serve] {app.stats.messages} request batches, {app.stats.items} tokens "
        f"generated in {dt:.1f}s ({app.stats.items/dt:.1f} tok/s)"
    )
    print(f"[serve] bus: step_time={bus.value('serve.step_time', stream='serve'):.3f}s "
          f"tokens_per_sec={bus.value('serve.tokens_per_sec', stream='serve'):.0f}")
    svc.cancel()


if __name__ == "__main__":
    main()
