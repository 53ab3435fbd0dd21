#!/usr/bin/env python3
"""Bring the main pipelines up on a TPU and check what they compute.

    python chip_smoke.py [--seed N]                # one chip, three phases
    python chip_smoke.py --four-chips [--seed N]   # elastic training, 4 chips

One chip runs three phases in this one process, each through the normal
path (``Pipeline.named(...)`` -> PipelineSpec -> PipelineRun -> engine ->
broker) with every input generated from ``--seed``:

(a) light-source reconstruction: the ``lightsource`` template source at the
    paper's 360 angles x 1448 columns (f32, ~2 MB a frame) on the shm data
    plane, into a ``gridrec`` and an ``mlem`` stage (``use_kernel=True``,
    n = 1448; mlem co-located on gridrec's one-chip pilot). Each stage's
    last reconstruction is compared with the jnp reference
    (``kernels/tomo/ref.py``) on the same frame, computed on the chip in
    f32 (``default_matmul_precision("highest")``);
(b) streaming KMeans: the ``cluster`` source, 5000 x 3 points a message and
    10 clusters, into the ``kmeans`` stage (``use_kernel=True``); the
    centroids are compared with ``use_kernel=False`` replayed on the same
    messages in the same order;
(c) LM serving: smollm-135m at its published widths with random weights
    from the seed, ``LMServeApp(mode="continuous", use_kernel=True)``
    serving 16 requests of the seeded heavy-tail trace. Each request's
    first decode-step logits through the Pallas decode kernel are compared
    with the dense ``model.decode`` path (``use_kernel=False``); how many
    served greedy tokens agree with dense greedy decoding is reported, not
    required.

``--four-chips`` runs only the paper's headline path across chips and what
it is compared with: an ``lm_train`` stage (smollm-135m, 8 sequences of 256
a step) takes 3 steps on a one-device pilot, an extension pilot adds 3
devices (``PilotComputeDescription(parent=...)``), ``LMTrainApp.on_rescale``
reshards onto a (4, 1) data mesh, and the stage takes 3 more steps; the
same 6 steps then run on one chip from the same seed and batches.

Every program routed through a Pallas kernel is lowered again and its
compiled text must hold the kernel (``tpu_custom_call``): nothing on this
path runs in interpret mode. The script refuses to run without a TPU.
Earlier lines are one JSON object per phase (wall times there are set-up
times, compiles included, not measurements); the last line is
``{"ok": true, "device": {...}}``. Any failure exits non-zero before it.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from dataclasses import dataclass
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.broker.consumer import Consumer, ConsumerGroup  # noqa: E402
from repro.core import PilotComputeDescription  # noqa: E402
from repro.kernels.tomo import ref as tomo_ref  # noqa: E402
from repro.miniapps import LMServeApp, LMTrainApp, StreamingKMeans  # noqa: E402
from repro.models.attention import decode_kernel_scope  # noqa: E402
from repro.pipeline import Pipeline  # noqa: E402
from repro.utils import enable_compile_cache  # noqa: E402

# -- tolerances, each against its reference ----------------------------------

#: relative L2, kernel reconstruction vs the f32 jnp reference. Both are f32
#: end to end (the kernels run on the VPU; the reference's matmuls are forced
#: to f32 on the MXU), so only summation order differs. Measured on a v5e:
#: 3.7e-7 (gridrec) and 5.7e-7 (mlem, 4 iterations).
TOMO_RTOL = 1e-5
#: max |centroid difference|, kernel vs jnp assignment. Both compute f32
#: distances, so labels agree except at exact near-ties; one flipped point
#: of a 5000-point message moves a centroid by (1 - decay) * |dp| / count,
#: about 1e-4 here.
KMEANS_ATOL = 1e-3
#: max |logit difference| / max |logit|, Pallas decode kernel vs the dense
#: decode, as served. The two attentions differ in the last bits (the
#: kernel's matmuls are f32-exact, the dense einsums take the TPU's default
#: bf16 pass), and the published config computes in bf16: each difference
#: that flips a bf16 rounding moves an activation by up to 2^-8 relative,
#: and 30 layers carry such flips to the logits. Measured: 1.5e-2 on a v5e;
#: 4.7e-3 on CPU with f32 matmuls, so the flips, not the kernel, set it.
LOGITS_RTOL = 5e-2
#: relative loss difference, 4-chip elastic run vs the same steps on one chip.
#: The first three steps run the same one-device program; after the reshard
#: the data-parallel gradient sum changes the reduction order, and bf16
#: matmul passes make that visible in the last digits of the loss. Measured
#: on a v5e host: 0 for the first three steps, 2.4e-5 at most after.
LOSS_RTOL = 1e-3

#: Pallas kernels appear in a compiled TPU program as custom calls
KERNEL_MARK = "tpu_custom_call"


@dataclass(frozen=True)
class Sizes:
    """What each phase runs. The defaults are the sizes the checks are for."""

    n_angles: int = 360
    n_det: int = 1448
    n: int = 1448
    frames: int = 24
    mlem_iters: int = 4
    points_per_msg: int = 5000
    n_clusters: int = 10
    kmeans_messages: int = 20
    arch: object = "smollm-135m"  # registry name or ArchConfig
    requests: int = 16
    prompt_len: int = 64
    gen_tokens: int = 16
    page_size: int = 16
    train_seq_len: int = 256
    train_seqs: int = 8


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def assert_kernel_compiled(name: str, jitted, *args) -> None:
    """The program ``jitted`` compiles for ``args`` must hold a kernel."""
    text = jitted.lower(*args).compile().as_text()
    if KERNEL_MARK not in text:
        raise AssertionError(f"{name}: compiled program holds no Pallas kernel")


def rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def device_of(x) -> str:
    (dev,) = x.devices()
    return f"{dev.platform}:{dev.id} ({dev.device_kind})"


def read_topic(cluster, topic: str, n: int, timeout: float = 60.0) -> list:
    """The first ``n`` messages of ``topic`` in log order (a fresh group)."""
    group = ConsumerGroup(cluster, "chip-smoke-reference", topic)
    consumer = Consumer(cluster, group, member_id="chip-smoke-reference")
    msgs, deadline = [], time.monotonic() + timeout
    while len(msgs) < n:
        if time.monotonic() > deadline:
            raise TimeoutError(f"read {len(msgs)}/{n} messages of {topic!r}")
        msgs.extend(consumer.poll(max_records=n - len(msgs), timeout=0.5))
    return msgs


def finish(run, stages) -> None:
    """Stop and await each stream here, so its error is raised rather than
    swallowed by the service's teardown."""
    for name in stages:
        run.stream(name).stop()


# -- (a) light-source reconstruction -----------------------------------------


def lightsource_phase(seed: int, sz: Sizes) -> dict:
    t0 = time.monotonic()
    frame_bytes = sz.n_angles * sz.n_det * 4
    spec = (Pipeline.named("chip-smoke-lightsource")
            .broker(nodes=1, transport="shm",
                    transport_options={"slot_bytes": 2 * frame_bytes, "n_slots": 8})
            .topic("frames", partitions=1)
            .source("frames", kind="lightsource", total_messages=sz.frames, seed=seed,
                    n_angles=sz.n_angles, n_det=sz.n_det)
            .stage("gridrec", topic="frames", processor="gridrec", transport="shm",
                   batch_interval=0.05, max_batch_records=1, n=sz.n, use_kernel=True)
            .stage("mlem", topic="frames", processor="mlem", transport="shm",
                   colocate_with="gridrec", batch_interval=0.05, max_batch_records=1,
                   n=sz.n, mlem_iters=sz.mlem_iters, use_kernel=True)
            .build())
    with spec.run() as run:
        for name in ("gridrec", "mlem"):
            run.await_batches(name, sz.frames, timeout=900)
        finish(run, ("gridrec", "mlem"))
        frame = jnp.asarray(run.source("frames").frame)
        recon = {name: run.stream(name).state for name in ("gridrec", "mlem")}
        apps = {name: run.processor(name) for name in ("gridrec", "mlem")}
    if run.errors:
        raise run.errors[0]

    angles = jnp.linspace(0, jnp.pi, sz.n_angles, endpoint=False)
    with jax.default_matmul_precision("highest"):
        refs = {
            "gridrec": jax.jit(tomo_ref.gridrec_ref, static_argnums=2)(frame, angles, sz.n),
            "mlem": jax.jit(functools.partial(
                tomo_ref.mlem_ref, n=sz.n, iters=sz.mlem_iters))(frame, angles),
        }
    out = {"phase": "lightsource", "frames": sz.frames,
           "frame_shape": list(frame.shape), "n": sz.n}
    for name in ("gridrec", "mlem"):
        assert recon[name].shape == (sz.n, sz.n), recon[name].shape
        assert bool(jnp.isfinite(recon[name]).all()), f"{name}: non-finite output"
        err = rel_l2(recon[name], refs[name])
        if not err <= TOMO_RTOL:
            raise AssertionError(f"{name}: rel L2 {err} vs reference > {TOMO_RTOL}")
        assert_kernel_compiled(name, apps[name].programs["frame"], frame, angles)
        out[name] = {"rel_l2_vs_ref": err, "rtol": TOMO_RTOL, "device": device_of(recon[name]),
                     "compiles": apps[name].compiles, "batches": apps[name].stats.batches}
    out["setup_s"] = time.monotonic() - t0
    return out


# -- (b) streaming KMeans ------------------------------------------------------


def kmeans_phase(seed: int, sz: Sizes) -> dict:
    t0 = time.monotonic()
    spec = (Pipeline.named("chip-smoke-kmeans")
            .broker(nodes=1)
            .topic("points", partitions=1)
            .source("points", kind="cluster", total_messages=sz.kmeans_messages, seed=seed,
                    n_clusters=sz.n_clusters, dim=3, points_per_msg=sz.points_per_msg)
            .stage("kmeans", topic="points", processor="kmeans", batch_interval=0.05,
                   max_batch_records=1, n_clusters=sz.n_clusters, dim=3,
                   use_kernel=True, seed=seed)
            .build())
    with spec.run() as run:
        run.await_batches("kmeans", sz.kmeans_messages, timeout=600)
        finish(run, ("kmeans",))
        centroids = run.stream("kmeans").state
        app = run.processor("kmeans")
        msgs = read_topic(run.cluster, "points", sz.kmeans_messages)
    if run.errors:
        raise run.errors[0]

    # the same messages in the stage's order (one partition, one message a
    # batch), through the jnp assignment in f32
    ref = StreamingKMeans(n_clusters=sz.n_clusters, dim=3, use_kernel=False, seed=seed)
    ref_c = None
    with jax.default_matmul_precision("highest"):
        for m in msgs:
            ref_c = ref.process(ref_c, [m])
        ref.sync()
    assert centroids.shape == (sz.n_clusters, 3)
    assert bool(jnp.isfinite(centroids).all()), "kmeans: non-finite centroids"
    err = float(jnp.max(jnp.abs(centroids - ref_c)))
    if not err <= KMEANS_ATOL:
        raise AssertionError(f"kmeans: max centroid diff {err} > {KMEANS_ATOL}")
    rows = app.buckets.fit(sz.points_per_msg)
    assert_kernel_compiled("kmeans", app.programs["step"],
                           jnp.zeros((rows, 3), jnp.float32), centroids, sz.points_per_msg)
    return {"phase": "kmeans", "messages": len(msgs), "points_per_msg": sz.points_per_msg,
            "max_centroid_diff_vs_ref": err, "atol": KMEANS_ATOL,
            "inertia_per_point": app.inertia, "device": device_of(centroids),
            "compiles": app.compiles, "setup_s": time.monotonic() - t0}


# -- (c) LM serving through the continuous batcher -----------------------------


def serving_phase(seed: int, sz: Sizes) -> dict:
    t0 = time.monotonic()
    spec = (Pipeline.named("chip-smoke-serving")
            .broker(nodes=1)
            .topic("requests", partitions=1)
            .source("requests", kind="serving_trace", total_messages=sz.requests, seed=seed,
                    vocab_size=_arch(sz).vocab_size, max_prompt=sz.prompt_len)
            .stage("serve", topic="requests", processor="lm_serve", batch_interval=0.05,
                   max_batch_records=4, cfg=sz.arch, mode="continuous", use_kernel=True,
                   prompt_len=sz.prompt_len, gen_tokens=sz.gen_tokens, batch=1,
                   page_size=sz.page_size, seed=seed)
            .build())
    with spec.run() as run:
        app, stream = run.processor("serve"), run.stream("serve")
        while app.stats.messages < sz.requests:  # batches hold 1-4 requests
            run.await_batches("serve", stream.stats.batches + 1, timeout=900)
        finish(run, ("serve",))
        params = run.stream("serve").state
        msgs = read_topic(run.cluster, "requests", sz.requests)
    if run.errors:
        raise run.errors[0]
    served = app.batcher.results  # request id (= message order) -> response
    assert sorted(served) == list(range(sz.requests)), sorted(served)

    model = app.model
    rows = np.stack([_pad_prompt(m.value, sz.prompt_len) for m in msgs])
    prefill = jax.jit(model.prefill)
    # two jitted decodes: jit caches on the function, not on the kernel
    # scope active while it traces
    dense_decode = jax.jit(lambda *a: model.decode(*a))
    kernel_decode = jax.jit(lambda *a: model.decode(*a))
    errs, first_tok_agree, second_tok_agree = [], 0, 0
    for i, row in enumerate(rows):
        logits, cache = prefill(params, {"tokens": jnp.asarray(row[None])})
        tok0 = int(jnp.argmax(logits[0, -1]))
        cache = jax.tree.map(  # room for the decoded entry, in whole pages
            lambda c: jnp.pad(c, [(0, 0)] * 2 + [(0, sz.page_size)] + [(0, 0)] * (c.ndim - 3)),
            cache)
        batch = {"tokens": jnp.array([[tok0]], jnp.int32),
                 "positions": jnp.array([sz.prompt_len], jnp.int32)}
        dense, _ = dense_decode(params, cache, batch)
        with decode_kernel_scope(block_kv=sz.page_size):
            kern, _ = kernel_decode(params, cache, batch)
        errs.append(float(jnp.max(jnp.abs(kern - dense)) / jnp.max(jnp.abs(dense))))
        tokens = served[i]["tokens"]
        first_tok_agree += tok0 == tokens[0]
        second_tok_agree += int(jnp.argmax(kern[0, -1])) == tokens[1]
        if not np.isfinite(np.asarray(kern)).all():
            raise AssertionError(f"request {i}: non-finite kernel logits")
    if not max(errs) <= LOGITS_RTOL:
        raise AssertionError(f"decode logits: max rel diff {max(errs)} > {LOGITS_RTOL}")
    with decode_kernel_scope(block_kv=sz.page_size):
        assert_kernel_compiled("first-step decode", kernel_decode, params, cache, batch)
    _assert_served_decode_has_kernel(app)

    dense_app = LMServeApp(model.cfg, mode="lockstep", prompt_len=sz.prompt_len,
                           gen_tokens=sz.gen_tokens, batch=1)
    greedy = dense_app.generate_tokens(params, msgs)
    got = np.array([served[i]["tokens"] for i in range(sz.requests)])
    return {"phase": "serving", "arch": model.cfg.name, "d_model": model.cfg.d_model,
            "n_layers": model.cfg.n_layers, "requests": sz.requests,
            "gen_tokens": sz.gen_tokens,
            "max_first_decode_logit_rel_diff": max(errs), "rtol": LOGITS_RTOL,
            "first_token_agree": int(first_tok_agree), "second_token_agree": int(second_tok_agree),
            "greedy_tokens_agree": f"{int((got == greedy).sum())}/{got.size}",
            "greedy_requests_identical": int((got == greedy).all(axis=1).sum()),
            "device": device_of(jax.tree.leaves(params)[0]),
            "decode_compiles": app.compiles, "prefill_compiles": app.prefill_compiles,
            "setup_s": time.monotonic() - t0}


def _arch(sz: Sizes):
    from repro.configs.registry import get_arch

    return get_arch(sz.arch) if isinstance(sz.arch, str) else sz.arch


def _pad_prompt(value, prompt_len: int) -> np.ndarray:
    """A request row as LMServeApp serves it: right-padded to prompt_len."""
    row = np.asarray(value)[0, :prompt_len].astype(np.int32)
    return np.pad(row, (0, prompt_len - row.shape[0]))


def _assert_served_decode_has_kernel(app) -> None:
    """Lower the batcher's decode step at a one-row bucket of the shape it
    served, against its own page pools."""
    b = app.batcher
    mp = b.pages_buckets.fit(b.cache.pool.pages_for(app.prompt_len + app.gen_tokens))
    assert_kernel_compiled(
        "served decode", b.programs["decode"], b.params, b.cache.k, b.cache.v,
        jnp.zeros((1, 1), jnp.int32), jnp.zeros((1,), jnp.int32),
        jnp.zeros((1, mp), jnp.int32))


# -- four chips: extend a training stage's pilot at runtime ------------------------


def elastic_training_phase(seed: int, sz: Sizes, devices: list) -> dict:
    t0 = time.monotonic()
    cfg = _arch(sz)
    tokens = dict(vocab_size=cfg.vocab_size, seq_len=sz.train_seq_len,
                  seqs_per_msg=sz.train_seqs)
    spec = (Pipeline.named("chip-smoke-elastic-train")
            .broker(nodes=1)
            .topic("tokens", partitions=1)
            .source("tokens", kind="tokens", total_messages=3, seed=seed, **tokens)
            # paused until the pilot has grown: steps 4-6 run on the new mesh
            .source("tokens", kind="tokens", total_messages=3, seed=seed + 1,
                    rate_msgs_per_s=0, **tokens)
            .stage("train", topic="tokens", processor="lm_train", batch_interval=0.05,
                   max_batch_records=1, cfg=sz.arch, seqs_per_step=sz.train_seqs,
                   seq_len=sz.train_seq_len, seed=seed)
            .build())
    with spec.run(devices=devices) as run:
        run.await_batches("train", 3, timeout=900)
        stream, app = run.stream("train"), run.processor("train")
        base = run.pilot("train")
        before = _placement(stream.state, base.plugin.devices)
        run.service.submit_pilot(PilotComputeDescription(
            number_of_nodes=len(devices) - 1, framework="spark", parent=base))
        leased = list(base.plugin.devices)
        run.source("tokens", 1).set_rate(None)
        run.await_batches("train", 6, timeout=900)
        finish(run, ("train",))
        after = _placement(stream.state, leased)
        mesh_after = dict(app.mesh.shape)
        losses = list(app.losses)
        msgs = read_topic(run.cluster, "tokens", 6)
    if run.errors:
        raise run.errors[0]

    ref = LMTrainApp(sz.arch, seqs_per_step=sz.train_seqs, seq_len=sz.train_seq_len, seed=seed)
    state = None
    for m in msgs:
        state = ref.process(state, [m])
    ref_losses = list(ref.losses)
    assert len(losses) == len(ref_losses) == 6, (losses, ref_losses)
    diffs = [abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)]
    if not (np.isfinite(losses).all() and max(diffs) <= LOSS_RTOL):
        raise AssertionError(f"losses {losses} vs one chip {ref_losses} (rtol {LOSS_RTOL})")
    return {"phase": "elastic_training", "arch": cfg.name, "steps": 6,
            "devices_before": before, "devices_after": after,
            "mesh_after": mesh_after, "losses": losses,
            "one_chip_losses": ref_losses, "max_loss_rel_diff": max(diffs),
            "rtol": LOSS_RTOL, "setup_s": time.monotonic() - t0}


def _placement(state, leased) -> list:
    """The params' devices, which must be exactly the stage's lease."""
    got = set()
    for leaf in jax.tree.leaves(state["params"]):
        got |= leaf.sharding.device_set
    if got != set(leased):
        raise AssertionError(f"params on {sorted(d.id for d in got)}, "
                             f"lease is {sorted(d.id for d in leased)}")
    return sorted(d.id for d in got)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the elastic training path across four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {devices[0].platform}); nothing is "
              "run in interpret mode here", file=sys.stderr)
        return 1
    enable_compile_cache()
    sz = Sizes()
    if args.four_chips:
        if len(devices) < 4:
            print(f"chip_smoke: --four-chips needs 4 TPU devices, found {len(devices)}",
                  file=sys.stderr)
            return 1
        emit(elastic_training_phase(args.seed, sz, devices[:4]))
    else:
        emit(lightsource_phase(args.seed, sz))
        emit(kmeans_phase(args.seed, sz))
        emit(serving_phase(args.seed, sz))
    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind, "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
