"""MASA — Mini-App for Streaming Analysis (paper §5).

Pluggable processors for the micro-batch engine:

* ``StreamingKMeans``   — score + decayed centroid update (paper Table 1)
* ``ReconstructionApp`` — GridRec / ML-EM per frame (paper §3.2.2, Fig. 9)
* ``LMTrainApp``        — streaming LM training (micro-batch train_step)
* ``LMServeApp``        — streaming LM inference (prefill/decode)

Each exposes ``process(state, msgs) -> state`` for
``MicroBatchPlugin.stream`` plus an ``on_rescale(devices)`` hook used by the
elastic path (live state resharding).

Hot-path design (docs/perf.md): variable-length batches are padded to a
small set of shape buckets so steady state never recompiles; frames of
one shape are stacked into one vmapped call per micro-batch; results are
double-buffered (``streaming.dispatch.AsyncWindow``, ``ASYNC_DEPTH``) so
batch N+1 dispatches while N executes, syncing only at
stats/checkpoint/rescale boundaries; and ``use_kernel=True`` routes
through the Pallas kernels, which compile natively on a TPU (the CPU
tests run them in interpret mode; ``interpret=None`` derives that in
``repro.kernels``).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.elastic.metrics import span
from repro.kernels import kmeans as kmeans_ops
from repro.kernels import tomo as tomo_ops
from repro.streaming.dispatch import (
    AsyncWindow,
    LatencyWindow,
    ShapeBuckets,
    compile_count,
    pad_rows,
)

#: batches a processor keeps in flight before it blocks on the oldest one
#: (double buffering: batch N+1 dispatches while N executes)
ASYNC_DEPTH = 2


def _arch(cfg):
    """An ArchConfig, or a registry name for one (keeps specs JSON-able)."""
    if isinstance(cfg, str):
        from repro.configs.registry import get_arch

        return get_arch(cfg)
    return cfg


@dataclass
class AppStats:
    messages: int = 0
    items: int = 0
    batches: int = 0
    compute_time: float = 0.0
    latency: LatencyWindow = field(default_factory=LatencyWindow)

    @property
    def msgs_per_sec(self) -> float:
        return self.messages / self.compute_time if self.compute_time else 0.0


class _HotPathApp:
    """Shared double-buffering plumbing for the MASA processors.

    Subclasses dispatch work with :meth:`_submit` and override
    :meth:`_on_complete` to fold a finished batch's (tiny, already-computed)
    outputs into their exposed attributes. ``sync()`` is the barrier the
    engine calls at checkpoint/rescale boundaries; stats accessors that need
    completed results call it implicitly.
    """

    #: whether each record's output depends on that record alone, not on how
    #: records are grouped into batches: the micro-batch engine then closes a
    #: window once it holds records and ``busy`` is false. A fact of the
    #: algorithm, not an option; processors that fold a batch into state keep
    #: the engine's clock window
    record_wise = False

    def _init_hotpath(self, *, metrics: Any = None, name: str | None = None) -> None:
        self.stats = AppStats()
        self.metrics = metrics
        self._metrics_name = name or type(self).__name__
        self._window = AsyncWindow(ASYNC_DEPTH, self.stats.latency)

    def _submit(self, result: Any, meta: Any = None, t0: float | None = None) -> None:
        """Enqueue a dispatched batch; ``t0`` = start of the batch's host
        work, so drained latencies span prep+compute. ``compute_time`` sums
        those per-batch completion latencies: the per-batch cost, not mere
        dispatch time, when batches overlap."""
        for res, m, dt in self._window.push(result, meta, t0=t0):
            self.stats.compute_time += dt
            self._on_complete(res, m, dt)
            self._publish_latency()

    def sync(self) -> None:
        """Block until every in-flight batch has completed (the
        stats/checkpoint/rescale barrier — see docs/perf.md)."""
        done = self._window.sync()
        if not done:
            return
        for res, m, dt in done:
            self.stats.compute_time += dt
            self._on_complete(res, m, dt)
        self._publish_latency()

    def _on_complete(self, result: Any, meta: Any, dt: float) -> None:
        pass

    def reset_stats(self) -> None:
        """Sync and zero the counters (benchmarks: exclude warmup batches)."""
        self.sync()
        self.stats = AppStats()
        self._window.latency = self.stats.latency

    def _publish_latency(self) -> None:
        if self.metrics is None or len(self.stats.latency) == 0:
            return
        lat, labels = self.stats.latency, {"app": self._metrics_name}
        self.metrics.publish("app.latency_p50", lat.p50, **labels)
        self.metrics.publish("app.latency_p99", lat.p99, **labels)

    @property
    def in_flight(self) -> int:
        return self._window.in_flight

    @property
    def busy(self) -> bool:
        """Whether a dispatched batch is unfinished on the device (does not
        block; see ``AsyncWindow.busy``)."""
        return self._window.busy


class StreamingKMeans(_HotPathApp):
    """Assign incoming points to centroids, update the model with decay.

    Each batch is padded up to a power-of-two row bucket and runs the masked
    update: the centroids are bit-identical to the unpadded update, and
    there are at most ``len(buckets)`` compiles however batch sizes vary.
    """

    def __init__(self, n_clusters: int = 10, dim: int = 3, *, decay: float = 0.9,
                 use_kernel: bool = False, seed: int = 0,
                 buckets: ShapeBuckets | None = None,
                 interpret: bool | None = None, metrics: Any = None):
        rng = np.random.default_rng(seed)
        self.centroids = jnp.asarray(rng.normal(size=(n_clusters, dim)), jnp.float32)
        self.decay = decay
        self.use_kernel = use_kernel
        self.buckets = buckets or ShapeBuckets(min_size=512, max_size=65536)
        self._init_hotpath(metrics=metrics, name="kmeans")
        self._inertia = float("nan")

        # named programs: the device trace shows them as jit_<name>
        def kmeans_step(points, centroids, n_valid):
            return kmeans_ops.minibatch_update_masked(
                points, centroids, n_valid,
                decay=decay, use_kernel=use_kernel, interpret=interpret)

        self._step = jax.jit(kmeans_step)

    def process(self, state, msgs):
        centroids = state if state is not None else self.centroids
        with span("app.prep") as prep:
            pts = np.concatenate([np.asarray(m.value) for m in msgs]).astype(np.float32)
            n = pts.shape[0]
            prep.set_metadata(items=n)
            t0 = time.monotonic()
            points = jnp.asarray(pad_rows(pts, self.buckets.fit(n)))
        # n is a dynamic scalar: every size sharing a bucket reuses the same
        # executable
        with span("app.dispatch", program="kmeans_step"):
            centroids, labels, inertia = self._step(points, centroids, n)
        self.stats.messages += len(msgs)
        self.stats.items += n
        self.stats.batches += 1
        self._submit(centroids, meta=(inertia, n), t0=t0)
        return centroids

    def _on_complete(self, result, meta, dt):
        inertia, n = meta
        self._inertia = float(inertia) / max(n, 1)

    @property
    def inertia(self) -> float:
        """Mean inertia of the most recent batch (syncs in-flight work)."""
        self.sync()
        return self._inertia

    @property
    def compiles(self) -> int:
        return compile_count(self._step)

    @property
    def programs(self) -> dict:
        """The jitted programs this processor dispatches, by name — for
        callers that lower them to inspect what was compiled."""
        return {"step": self._step}

    def on_rescale(self, devices):
        # centroids are tiny: re-placement is a device_put
        def f(state):
            return jax.device_put(state, devices[0]) if state is not None else state
        return f


class ReconstructionApp(_HotPathApp):
    """Per-frame tomographic reconstruction (GridRec or ML-EM).

    A micro-batch's frames are grouped by sinogram shape; each group of more
    than one frame is stacked and reconstructed in one vmapped call, its
    depth padded to a small bucket set so the compile count stays bounded.
    """

    #: each frame's reconstruction depends only on that frame
    record_wise = True

    def __init__(self, algorithm: str = "gridrec", *, n: int = 64, mlem_iters: int = 4,
                 use_kernel: bool = False, batch_buckets: ShapeBuckets | None = None,
                 interpret: bool | None = None, metrics: Any = None):
        assert algorithm in ("gridrec", "mlem")
        self.algorithm = algorithm
        self.n = n
        self.use_kernel = use_kernel
        self.batch_buckets = batch_buckets or ShapeBuckets(min_size=1, max_size=8)
        self._init_hotpath(metrics=metrics, name=algorithm)
        self._angles_cache: dict[int, jax.Array] = {}

        # named programs: the device trace shows them as jit_<name>
        def gridrec_frame(sino, angles):
            return tomo_ops.gridrec(sino, angles, n, use_kernel=use_kernel,
                                    interpret=interpret)

        def gridrec_stack(sinos, angles):
            return tomo_ops.gridrec_batch(sinos, angles, n, use_kernel=use_kernel,
                                          interpret=interpret)

        def mlem_frame(sino, angles):
            return tomo_ops.mlem(sino, angles, n, iters=mlem_iters,
                                 use_kernel=use_kernel, interpret=interpret)

        def mlem_stack(sinos, angles):
            return tomo_ops.mlem_batch(sinos, angles, n, iters=mlem_iters,
                                       use_kernel=use_kernel, interpret=interpret)

        one, many = ((gridrec_frame, gridrec_stack) if algorithm == "gridrec"
                     else (mlem_frame, mlem_stack))
        self._program_names = (one.__name__, many.__name__)
        self._rec = jax.jit(one)
        self._rec_batch = jax.jit(many)

    def _angles(self, n_angles: int) -> jax.Array:
        """Per-shape cache: the same angle grid is re-used for every frame of
        that sinogram shape instead of re-materializing per message."""
        a = self._angles_cache.get(n_angles)
        if a is None:
            a = self._angles_cache[n_angles] = jnp.linspace(
                0, jnp.pi, n_angles, endpoint=False)
        return a

    def process(self, state, msgs):
        t0 = time.monotonic()
        with span("app.prep", frames=len(msgs)):
            groups: dict[tuple, list[np.ndarray]] = {}
            for m in msgs:
                sino = np.asarray(m.value, np.float32)
                groups.setdefault(sino.shape, []).append(sino)
            last_shape = np.asarray(msgs[-1].value).shape
            # (shape, frames, device input, angles) per shape group
            inputs = []
            for shape, frames in groups.items():
                if len(frames) == 1:
                    x = frames[0]
                else:
                    x = pad_rows(np.stack(frames), self.batch_buckets.fit(len(frames)))
                inputs.append((shape, len(frames), jnp.asarray(x), self._angles(shape[0])))
        frame_program, stack_program = self._program_names
        recon = None
        for shape, n_frames, x, angles in inputs:
            if n_frames == 1:
                # the scalar path beats a B=1 batched matmul (degenerate gemm)
                with span("app.dispatch", program=frame_program):
                    rec = self._rec(x, angles)
            else:
                with span("app.dispatch", program=stack_program):
                    recs = self._rec_batch(x, angles)
                rec = recs[n_frames - 1]
            # state contract: the LAST message's reconstruction (its frame is
            # the last element of its shape group)
            if shape == last_shape:
                recon = rec
        self.stats.messages += len(msgs)
        self.stats.items += len(msgs)
        self.stats.batches += 1
        self._submit(recon, t0=t0)
        return recon  # last reconstruction = state (exposed for inspection)

    @property
    def compiles(self) -> int:
        """Compiles of every program the processor dispatches (single-frame
        groups run through the scalar program, larger ones the stack)."""
        return compile_count(self._rec) + compile_count(self._rec_batch)

    @property
    def programs(self) -> dict:
        """The jitted programs this processor dispatches, by name: ``frame``
        takes (sinogram, angles), ``batch`` a stack of sinograms."""
        return {"frame": self._rec, "batch": self._rec_batch}


class LMTrainApp(_HotPathApp):
    """Streaming LM training: consume token messages, run train steps.

    State = (params, opt_state). Without an explicit ``mesh`` the stage
    starts on one device (``jax.devices()[0]``, the first device a pilot
    leases); rescale re-lowers the step on a data mesh over exactly the
    devices it is handed and device_puts the live state (checkpoint-free
    migration). The train
    step donates params/opt-state buffers, and per-step losses are read
    back lazily at sync boundaries instead of forcing a device round-trip
    per batch.
    """

    def __init__(self, cfg, *, mesh=None, opt_cfg=None, seqs_per_step: int = 8,
                 seq_len: int = 128, metrics: Any = None, seed: int = 0):
        from repro.launch.mesh import make_mesh
        from repro.models import build_model
        from repro.configs.base import ShapeConfig
        from repro.runtime.steps import build_train_step

        self.cfg = cfg = _arch(cfg)
        self.seed = seed
        self.model = build_model(cfg)
        self.mesh = mesh or make_mesh((1, 1), ("data", "model"),
                                      devices=jax.devices()[:1])
        self.shape = ShapeConfig("stream", seq_len, seqs_per_step, "train")
        self.opt_cfg = opt_cfg
        self.bundle = build_train_step(self.model, self.mesh, self.shape, opt_cfg, donate=True)
        self._init_hotpath(metrics=metrics, name="lm_train")
        self._losses: list[float] = []

    def init_state(self):
        from repro.runtime.optimizer import Optimizer, OptimizerConfig

        params = self.model.init(jax.random.key(self.seed))
        opt = Optimizer(self.opt_cfg or OptimizerConfig(name=self.cfg.optimizer))
        return {"params": params, "opt": opt.init(params)}

    def process(self, state, msgs):
        if state is None:
            state = self.init_state()
        toks = np.concatenate([np.asarray(m.value) for m in msgs])  # (n_seqs, S)
        B = self.shape.global_batch
        n_steps = len(toks) // B
        t0 = time.monotonic()
        for s in range(max(n_steps, 1)):
            batch = toks[s * B : (s + 1) * B]
            if len(batch) < B:  # pad the tail window
                batch = np.concatenate([batch, np.zeros((B - len(batch), batch.shape[1] if batch.size else self.shape.seq_len), np.int32)])
            params, opt, metrics = self.bundle.fn(
                state["params"], state["opt"], {"tokens": jnp.asarray(batch, jnp.int32)}
            )
            state = {"params": params, "opt": opt}
        self.stats.messages += len(msgs)
        self.stats.items += int(len(toks)) * self.shape.seq_len
        self.stats.batches += 1
        self._submit(metrics["loss"], t0=t0)
        return state

    def _on_complete(self, result, meta, dt):
        self._losses.append(float(result))

    @property
    def losses(self) -> list[float]:
        """Per-batch final-step losses (syncs in-flight work)."""
        self.sync()
        return self._losses

    @property
    def compiles(self) -> int:
        return compile_count(self.bundle.fn)

    def on_rescale(self, devices):
        """Elastic: rebuild mesh over the new device set, reshard live state."""
        from repro.launch.mesh import make_mesh
        from repro.runtime.steps import build_train_step

        def f(state):
            self.sync()  # in-flight steps must land before buffers move
            self.mesh = make_mesh((len(devices), 1), ("data", "model"),
                                  devices=list(devices))
            self.bundle = build_train_step(self.model, self.mesh, self.shape, self.opt_cfg, donate=True)
            if state is not None:
                p_sh, o_sh, _ = self.bundle.in_shardings
                state = {
                    "params": jax.device_put(state["params"], p_sh),
                    "opt": jax.device_put(state["opt"], o_sh),
                }
            return state

        return f


class LMServeApp(_HotPathApp):
    """Streaming LM inference: prefill each request batch, decode n tokens.

    ``mode="lockstep"`` (default): the whole micro-batch's requests are
    stacked into one prefill (rows padded to a bucket) and the per-token
    decode loop runs as one fused ``lax.scan`` with the KV cache donated
    between steps — every row enters and exits together.

    ``mode="continuous"``: requests go through the in-flight batching
    scheduler (``repro.serving.ContinuousBatcher``) — prompts prefill into
    paged KV-cache slots and join the live decode batch mid-stream, finished
    rows exit per step and free their pages immediately. Same greedy tokens
    (see docs/serving.md for the equivalence argument), radically different
    tail latency under heavy-tail prompt lengths.
    """

    def __init__(self, cfg, *, mesh=None, prompt_len: int = 32, gen_tokens: int = 8,
                 batch: int = 4, metrics: Any = None,
                 row_buckets: ShapeBuckets | None = None, mode: str = "lockstep",
                 n_pages: int = 256, page_size: int = 16,
                 use_kernel: bool = False, interpret: bool | None = None,
                 seed: int = 0):
        from repro.models import build_model

        assert mode in ("lockstep", "continuous"), mode
        self.cfg = cfg = _arch(cfg)
        self.seed = seed
        self.model = build_model(cfg)
        # single-host serving jits the model directly; a mesh is only needed
        # when the caller shards params explicitly, so none is built here
        self.mesh = mesh
        self.prompt_len = prompt_len
        self.gen_tokens = gen_tokens
        self.batch = batch
        self.mode = mode
        self.row_buckets = row_buckets or ShapeBuckets(min_size=batch, max_size=batch * 8)
        self._init_hotpath(metrics=metrics, name="lm_serve")
        # cache sized for prompt + generation inside the jitted path: growing
        # it afterwards (jnp.pad on the host) copied the entire KV cache per
        # batch (see _prefill_grown)
        self._prefill = jax.jit(self._prefill_grown)
        # donate the KV cache: each scan step reuses the same buffers
        self._generate = jax.jit(self._generate_impl, donate_argnums=(1,))
        self._batcher = None
        if mode == "continuous":
            from repro.serving import ContinuousBatcher

            self._batcher = ContinuousBatcher(
                self.model, n_pages=n_pages, page_size=page_size,
                use_kernel=use_kernel, interpret=interpret,
                max_queue=max(64, batch * 16), metrics=metrics)
            self._rid = 0
            self._now = 0.0

    def _prefill_grown(self, params, batch):
        """Prefill with the KV cache allocated at prompt_len + gen_tokens —
        the pad happens inside the jit, so XLA materializes the full-size
        cache once instead of prefill-size buffers plus a host-side copy."""
        logits, cache = self.model.prefill(params, batch)
        cache = jax.tree.map(
            lambda c: jnp.pad(
                c, [(0, 0)] * 2 + [(0, self.gen_tokens)] + [(0, 0)] * (c.ndim - 3))
            if c.ndim >= 4 else c,
            cache,
        )
        return logits, cache

    def _generate_impl(self, params, cache, tok, pos):
        def step(carry, _):
            tok, pos, cache = carry
            pos = pos + 1
            logits, cache = self.model.decode(params, cache, {"tokens": tok, "positions": pos})
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (tok, pos, cache), tok

        (tok, _, _), toks = jax.lax.scan(
            step, (tok, pos, cache), None, length=self.gen_tokens - 1)
        return toks  # (gen_tokens-1, B, 1)

    def _stack_requests(self, msgs) -> np.ndarray:
        """(sum_i b_i, prompt_len) int32: every message's requests in one
        batch, right-padded to prompt_len columns."""
        rows = []
        for m in msgs:
            t = np.asarray(m.value)[: self.batch, : self.prompt_len].astype(np.int32)
            if t.shape[1] < self.prompt_len:
                t = np.pad(t, [(0, 0), (0, self.prompt_len - t.shape[1])])
            rows.append(t)
        return np.concatenate(rows)

    def _serve_batch(self, params, msgs):
        """One stacked prefill + fused scan decode for a whole micro-batch.
        Returns (seq (gen_tokens, B, 1) greedy tokens, n_req live rows)."""
        toks = self._stack_requests(msgs)
        n_req = toks.shape[0]
        tok_in = jnp.asarray(pad_rows(toks, self.row_buckets.fit(n_req)))
        logits, cache = self._prefill(params, {"tokens": tok_in})
        pos = jnp.full((tok_in.shape[0],), self.prompt_len - 1, jnp.int32)
        tok0 = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        if self.gen_tokens > 1:
            rest = self._generate(params, cache, tok0, pos)  # (T-1, B, 1)
            seq = jnp.concatenate([tok0[None], rest])
        else:
            seq = tok0[None]
        return seq, n_req

    def _serve_continuous(self, params, msgs) -> np.ndarray:
        """Route a micro-batch through the in-flight scheduler; returns
        (n_req, gen_tokens) greedy tokens in request order."""
        from repro.serving.trace import Request

        b = self._batcher
        b.params = params
        toks = self._stack_requests(msgs)
        rids = []
        for row in toks:
            r = Request(self._rid, self._now, tuple(int(t) for t in row),
                        self.gen_tokens)
            self._rid += 1
            verdict = b.submit(r, self._now)
            assert verdict != "reject", "drop-in mode must not shed requests"
            rids.append(r.rid)
            self._now += b.step(self._now)
        self._now = b.drain(self._now)
        return np.array([b.results[r]["tokens"] for r in rids], np.int32)

    def init_state(self):
        """Random weights from ``seed`` — the serving state when the stream
        starts without params."""
        return self.model.init(jax.random.key(self.seed))

    def process(self, state, msgs):
        # serving state = model params
        params = state if state is not None else self.init_state()
        t0 = time.monotonic()
        if self.mode == "continuous":
            out = self._serve_continuous(params, msgs)
            n_req = out.shape[0]
        else:
            out, n_req = self._serve_batch(params, msgs)
        self.stats.messages += len(msgs)
        self.stats.items += n_req * self.gen_tokens
        self.stats.batches += 1
        self._submit(out, t0=t0)
        return params

    def generate_tokens(self, params, msgs) -> np.ndarray:
        """Greedy tokens for a message batch: (n_req, gen_tokens) int32.
        Convenience/inspection path; ``process`` is the streaming hot path."""
        if self.mode == "continuous":
            return self._serve_continuous(params, msgs)
        seq, n_req = self._serve_batch(params, msgs)
        return np.asarray(seq[:, :n_req, 0]).T

    @property
    def compiles(self) -> int:
        if self.mode == "continuous":
            return self._batcher.decode_compiles
        return compile_count(self._generate)

    @property
    def batcher(self):
        """The continuous mode's ContinuousBatcher (``results`` holds each
        request's response by request id); None in lockstep mode."""
        return self._batcher

    @property
    def prefill_compiles(self) -> int:
        """Steady-state contract (satellite of docs/perf.md): one compile per
        row bucket — the in-jit cache growth must not retrigger per batch."""
        if self.mode == "continuous":
            return self._batcher.prefill_compiles
        return compile_count(self._prefill)


PROCESSORS = {
    "kmeans": StreamingKMeans,
    "gridrec": lambda **kw: ReconstructionApp("gridrec", **kw),
    "mlem": lambda **kw: ReconstructionApp("mlem", **kw),
    "lm_train": LMTrainApp,
    "lm_serve": LMServeApp,
}
