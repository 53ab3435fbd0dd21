"""Streaming-training driver: MASS token source -> broker -> micro-batch
train loop, with checkpointing and exactly-once offsets.

This is the paper's Type-2 pipeline (simulation/corpus -> analysis) with the
assigned LM architectures as the analysis stage. On CPU use a reduced config:

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
      --steps 20 --seq-len 128 --batch 8
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np

from repro.configs.registry import get_arch
from repro.checkpoint import CheckpointManager
from repro.core import PilotComputeService
from repro.elastic import MetricsBus
from repro.launch import instrumented
from repro.launch.mesh import make_mesh
from repro.miniapps import LMTrainApp, SourceConfig, TokenSource
from repro.utils import enable_compile_cache
from repro.runtime.optimizer import OptimizerConfig
from repro.scheduler import ResourceRequest


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true", help="tiny same-family config (CPU)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8, help="sequences per train step")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--broker-nodes", type=int, default=2)
    ap.add_argument("--partitions", type=int, default=4)
    ap.add_argument("--checkpoint-dir", default="/tmp/repro-train-ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    bus = MetricsBus()
    svc = PilotComputeService(metrics=bus)
    kafka = svc.submit_pilot({"number_of_nodes": args.broker_nodes, "type": "kafka"})
    cluster = kafka.get_context()
    cluster.create_topic("tokens", args.partitions)
    spark = svc.submit_pilot({"number_of_nodes": 1, "type": "spark"})
    ctx = spark.get_context()
    # file the training pilot's demand with the service's arbiter: a static
    # reservation today, but pipelines sharing this pool now see (and must
    # schedule around) the trainer's devices
    held = len(spark.lease.devices)
    svc.get_arbiter(bus).submit(ResourceRequest(
        "launch/train", min_devices=held, max_devices=held, target=held,
        current_fn=lambda: len(spark.lease.devices)))

    opt = OptimizerConfig(name=cfg.optimizer, learning_rate=args.lr, warmup_steps=5,
                          total_steps=max(args.steps, 10))
    # train on exactly the devices the pilot leased
    mesh = make_mesh((held, 1), ("data", "model"), devices=spark.lease.devices)
    app = LMTrainApp(cfg, mesh=mesh, opt_cfg=opt, seqs_per_step=args.batch,
                     seq_len=args.seq_len)
    ckpt = CheckpointManager(args.checkpoint_dir, keep_last=2, async_save=True)

    state = None
    if args.resume and ckpt.latest_step() is not None:
        template = app.init_state()
        state, meta = ckpt.restore(template)
        print(f"[train] resumed from step {ckpt.latest_step()} (offsets {meta.get('offsets')})")

    source = TokenSource(
        cluster,
        SourceConfig("tokens", total_messages=args.steps * 2 + 8, n_producers=2),
        vocab_size=cfg.vocab_size,
        seq_len=args.seq_len,
        seqs_per_msg=args.batch,
    ).start()

    def checkpoint_fn(state, offsets):
        step = app.stats.batches
        if step % args.checkpoint_every == 0 and state is not None:
            ckpt.save(step, state, meta={"offsets": offsets, "arch": cfg.name})

    stream = ctx.stream(
        cluster, "tokens", group="trainer",
        process_fn=instrumented(app, bus, "train"), state=state,
        batch_interval=0.2, max_batch_records=1, checkpoint_fn=checkpoint_fn,
        metrics=bus, metrics_label="train",
    ).start()

    t0 = time.time()
    stream.await_batches(args.steps, timeout=3600)
    stream.stop()
    source.stop()
    ckpt.wait()
    dt = time.time() - t0
    toks = app.stats.items
    print(
        f"[train] {app.stats.batches} steps, {toks} tokens in {dt:.1f}s "
        f"({toks/dt:.0f} tok/s); loss {app.losses[0]:.3f} -> {app.losses[-1]:.3f}"
    )
    print(f"[train] bus: step_time={bus.value('train.step_time', stream='train'):.3f}s "
          f"tokens_per_sec={bus.value('train.tokens_per_sec', stream='train'):.0f}")
    svc.cancel()


if __name__ == "__main__":
    main()
