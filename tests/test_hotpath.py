"""Streaming hot-path overhaul: shape-bucketed dispatch, masked bucket
padding, async double-buffering, kernel-vs-ref parity, and the engine's
latency publishing (ISSUE 2 / docs/perf.md)."""
import time
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.kmeans import minibatch_update, minibatch_update_masked, update_ref, update_scatter
from repro.kernels.tomo import gridrec, gridrec_batch, mlem, mlem_batch, project_ref, shepp_logan
from repro.miniapps import ReconstructionApp, StreamingKMeans
from repro.streaming.dispatch import (
    AsyncWindow,
    LatencyWindow,
    ShapeBuckets,
    compile_count,
    pad_rows,
)


@dataclass
class Msg:
    value: Any
    timestamp: float = 0.0


# ---------------------------------------------------------------------------
# dispatch primitives
# ---------------------------------------------------------------------------


def test_shape_buckets_fit():
    b = ShapeBuckets(min_size=512, max_size=4096)
    assert b.sizes == (512, 1024, 2048, 4096)
    assert b.fit(1) == 512
    assert b.fit(512) == 512
    assert b.fit(513) == 1024
    assert b.fit(4096) == 4096
    assert b.fit(5000) == 8192  # beyond max: next multiple of max
    assert b.fit(9000) == 12288
    assert len(b) == 4


def test_pad_rows():
    x = np.arange(6, dtype=np.float32).reshape(3, 2)
    p = pad_rows(x, 5)
    assert p.shape == (5, 2)
    assert (p[:3] == x).all() and (p[3:] == 0).all()
    assert pad_rows(x, 3) is x  # no-op when already at size


def test_async_window_bounds_in_flight_and_syncs():
    w = AsyncWindow(depth=2, latency=LatencyWindow())
    done = []
    for i in range(5):
        done += w.push(jnp.full((4,), i), meta=i)
        assert w.in_flight <= 2
    assert [m for _, m, _ in done] == [0, 1, 2]
    done += w.sync()
    assert [m for _, m, _ in done] == [0, 1, 2, 3, 4]
    assert w.in_flight == 0
    assert len(w.latency) == 5 and w.latency.p99 >= w.latency.p50 >= 0.0


def test_async_window_busy_reads_readiness_not_in_flight():
    """``busy`` is true while a pushed result computes and false once it is
    ready, though ``in_flight`` counts the entry until it is drained."""
    w = AsyncWindow(depth=2)
    assert not w.busy
    step = jax.jit(lambda x: jax.lax.fori_loop(0, 40, lambda i, a: jnp.tanh(a @ a), x))
    x = jnp.full((1024, 1024), 1e-3, jnp.float32)
    step(x).block_until_ready()  # compile outside the check
    result = step(x)
    w.push(result)
    assert w.busy and w.in_flight == 1
    jax.block_until_ready(result)
    assert not w.busy and w.in_flight == 1
    w.sync()
    assert not w.busy and w.in_flight == 0


def test_async_window_depth_zero_is_synchronous():
    w = AsyncWindow(depth=0)
    done = w.push(jnp.ones((2,)), meta="a")
    assert len(done) == 1 and w.in_flight == 0


def test_latency_window_quantiles():
    lw = LatencyWindow()
    for v in [0.1, 0.2, 0.3, 0.4, 10.0]:
        lw.record(v)
    assert lw.p50 == pytest.approx(0.3)
    assert lw.p99 > 1.0


# ---------------------------------------------------------------------------
# bucket padding correctness (masked update == unpadded, bit-identical)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,bucket", [(97, 128), (513, 1024), (1769, 2048), (5000, 8192)])
def test_masked_update_bit_identical_to_unpadded(n, bucket):
    rng = np.random.default_rng(n)
    pts = jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)
    cen = jnp.asarray(rng.normal(size=(10, 3)), jnp.float32)
    c_ref, l_ref, i_ref = minibatch_update(pts, cen, decay=0.8)
    padded = jnp.zeros((bucket, 3), jnp.float32).at[:n].set(pts)
    c_pad, l_pad, i_pad = minibatch_update_masked(padded, cen, n, decay=0.8)
    np.testing.assert_array_equal(np.asarray(c_ref), np.asarray(c_pad))
    np.testing.assert_array_equal(np.asarray(l_ref), np.asarray(l_pad[:n]))
    assert (np.asarray(l_pad[n:]) == -1).all()  # padding rows are flagged
    np.testing.assert_allclose(float(i_ref), float(i_pad), rtol=1e-6)


def test_update_scatter_matches_matmul_oracle():
    rng = np.random.default_rng(0)
    pts = jnp.asarray(rng.normal(size=(300, 4)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 7, 300), jnp.int32)
    s1, c1 = update_scatter(pts, labels, 7)
    s2, c2 = update_ref(pts, labels, 7)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_bucketed_kmeans_app_bit_identical_to_unpadded_reference():
    """The whole app path: padded bucketed dispatch + async double-buffering
    must reproduce, exactly, the plain update over each unpadded batch."""
    rng = np.random.default_rng(1)
    batches = [[Msg(rng.normal(size=(int(rng.integers(100, 1500)), 3)))] for _ in range(10)]
    app = StreamingKMeans(n_clusters=6, dim=3, seed=2)
    ref_step = jax.jit(lambda p, c: minibatch_update(p, c, decay=app.decay))
    state, ref = None, app.centroids
    for b in batches:
        state = app.process(state, b)
        pts = np.asarray(b[0].value, np.float32)
        ref, _, ref_inertia = ref_step(jnp.asarray(pts), ref)
    app.sync()
    np.testing.assert_array_equal(np.asarray(state), np.asarray(ref))
    assert app.inertia == pytest.approx(float(ref_inertia) / len(pts))
    assert app.stats.messages == 10


def test_kmeans_recompile_count_bounded_by_buckets():
    """N variable-sized batches must compile at most len(buckets) times."""
    buckets = ShapeBuckets(min_size=256, max_size=2048)
    app = StreamingKMeans(n_clusters=5, dim=3, buckets=buckets)
    rng = np.random.default_rng(3)
    state = None
    sizes = rng.integers(50, 2000, size=20)
    for n in sizes:
        state = app.process(state, [Msg(rng.normal(size=(int(n), 3)))])
    app.sync()
    assert app.compiles <= len(buckets)
    assert app.compiles == len({buckets.fit(int(n)) for n in sizes})
    # unbucketed, it would compile once per distinct size
    assert len(set(int(n) for n in sizes)) > len(buckets)


# ---------------------------------------------------------------------------
# use_kernel plumbing: kernel and ref paths agree (interpret mode on CPU)
# ---------------------------------------------------------------------------


def test_kmeans_streaming_kernel_matches_ref_path():
    rng = np.random.default_rng(5)
    batches = [[Msg(rng.normal(size=(int(rng.integers(100, 900)), 3)))] for _ in range(4)]
    kern = StreamingKMeans(n_clusters=6, dim=3, seed=4, use_kernel=True, interpret=True)
    ref = StreamingKMeans(n_clusters=6, dim=3, seed=4, use_kernel=False)
    s_k = s_r = None
    for b in batches:
        s_k = kern.process(s_k, b)
        s_r = ref.process(s_r, b)
    kern.sync(); ref.sync()
    assert kern.use_kernel and not ref.use_kernel
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("algorithm", ["gridrec", "mlem"])
def test_tomo_streaming_kernel_matches_ref_path(algorithm):
    n, a, nd = 32, 16, 48
    img = shepp_logan(n)
    angles = jnp.linspace(0, jnp.pi, a, endpoint=False)
    sino = np.asarray(project_ref(img, angles, nd))
    msgs = [Msg(sino), Msg(sino * 0.5)]
    kern = ReconstructionApp(algorithm, n=n, mlem_iters=2, use_kernel=True, interpret=True)
    ref = ReconstructionApp(algorithm, n=n, mlem_iters=2, use_kernel=False)
    out_k = kern.process(None, msgs)
    out_r = ref.process(None, msgs)
    kern.sync(); ref.sync()
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-3)


@pytest.mark.parametrize("fn_batch,fn_one,kw", [
    (gridrec_batch, gridrec, {}),
    (mlem_batch, mlem, {"iters": 2}),
])
def test_batched_reconstruction_matches_sequential(fn_batch, fn_one, kw):
    n, a, nd = 24, 8, 32
    img = shepp_logan(n)
    angles = jnp.linspace(0, jnp.pi, a, endpoint=False)
    sino = project_ref(img, angles, nd)
    stack = jnp.stack([sino, sino * 2.0, sino * 0.1])
    outs = fn_batch(stack, angles, n, **kw)
    for i in range(3):
        np.testing.assert_allclose(
            np.asarray(outs[i]), np.asarray(fn_one(stack[i], angles, n, **kw)),
            rtol=1e-5, atol=1e-5)


def _gridrec_reference(msg, n):
    """The plain reference: jitted gridrec of one frame on its own."""
    sino = jnp.asarray(np.asarray(msg.value), jnp.float32)
    angles = jnp.linspace(0, jnp.pi, sino.shape[0], endpoint=False)
    return jax.jit(gridrec, static_argnums=2)(sino, angles, n)


def test_reconstruction_batched_app_matches_loop_and_caches_angles():
    n, a, nd = 32, 16, 48
    img = shepp_logan(n)
    angles = jnp.linspace(0, jnp.pi, a, endpoint=False)
    sino = np.asarray(project_ref(img, angles, nd))
    msgs = [Msg(sino * (1 + 0.1 * i)) for i in range(3)]
    batched = ReconstructionApp("gridrec", n=n)
    out_b = batched.process(None, msgs)
    expected = _gridrec_reference(msgs[-1], n)
    batched.sync()
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(expected), rtol=1e-5, atol=1e-6)
    # angles hoisted into the per-shape cache: same jnp array object reused
    assert list(batched._angles_cache) == [a]
    first = batched._angles(a)
    batched.process(None, msgs)
    batched.sync()
    assert batched._angles(a) is first


def test_reconstruction_mixed_shapes_grouped():
    """A micro-batch with two sinogram shapes reconstructs both groups, and
    the returned state is the LAST message's reconstruction (the documented
    contract) even though its shape group was seen first."""
    n = 24
    img = shepp_logan(n)
    frames = []
    for i, (a, nd) in enumerate([(8, 32), (16, 32), (8, 32)]):
        angles = jnp.linspace(0, jnp.pi, a, endpoint=False)
        frames.append(Msg(np.asarray(project_ref(img, angles, nd)) * (1 + 0.1 * i)))
    app = ReconstructionApp("gridrec", n=n)
    out = app.process(None, frames)
    app.sync()
    assert out.shape == (n, n)
    assert sorted(app._angles_cache) == [8, 16]
    expected = _gridrec_reference(frames[-1], n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# engine integration: sync contract + latency publishing
# ---------------------------------------------------------------------------


def _pipeline(svc, app, n_msgs=8, **stream_kw):
    from repro.broker import Producer

    cluster = svc.submit_pilot({"number_of_nodes": 1, "type": "kafka"}).get_context()
    cluster.create_topic("t", 2)
    ctx = svc.submit_pilot({"number_of_nodes": 1, "type": "spark"}).get_context()
    prod = Producer(cluster, "t", serializer="npy")
    rng = np.random.default_rng(0)
    for _ in range(n_msgs):
        prod.send(rng.normal(size=(int(rng.integers(50, 400)), 3)))
    s = ctx.stream(cluster, "t", group="g", process_fn=app.process,
                   batch_interval=0.02, max_batch_records=2, backpressure=False,
                   **stream_kw)
    return s


@pytest.fixture
def svc():
    from repro.core import PilotComputeService

    s = PilotComputeService()
    yield s
    s.cancel()


def test_stream_auto_wires_sync_fn_from_bound_processor(svc):
    app = StreamingKMeans(n_clusters=4, dim=3)
    s = _pipeline(svc, app)
    assert s.sync_fn == app.sync
    s.start()
    s.await_batches(2, timeout=30)
    s.stop()  # stop() syncs: all dispatched batches must have landed
    assert app.in_flight == 0
    assert s.state.shape == (4, 3)


def test_engine_publishes_latency_quantiles_to_bus(svc):
    from repro.elastic.metrics import MetricsBus, MetricsSnapshot

    bus = MetricsBus()
    app = StreamingKMeans(n_clusters=4, dim=3)
    s = _pipeline(svc, app, metrics=bus)
    s.start()
    s.await_batches(2, timeout=30)
    s.stop()
    p50 = bus.value("stream.latency_p50", default=-1.0, stream="t")
    p99 = bus.value("stream.latency_p99", default=-1.0, stream="t")
    assert p50 >= 0.0 and p99 >= p50
    snap = MetricsSnapshot.capture(bus)
    assert snap.latency_p50 == p50 and snap.latency_p99 == p99


def test_checkpoint_boundary_syncs_in_flight_work(svc):
    order = []
    app = StreamingKMeans(n_clusters=4, dim=3)
    real_sync = app.sync

    def tracked_sync():
        order.append("sync")
        real_sync()

    def ckpt(state, offsets):
        order.append("ckpt")
        assert app.in_flight == 0  # the contract: drained before snapshot

    s = _pipeline(svc, app, checkpoint_fn=ckpt, sync_fn=tracked_sync)
    s.start()
    s.await_batches(2, timeout=30)
    s.stop()
    assert "sync" in order and "ckpt" in order
    assert order.index("sync") < order.index("ckpt")


def test_rescale_drains_window_before_reshard(svc):
    app = StreamingKMeans(n_clusters=4, dim=3)
    s = _pipeline(svc, app)
    s.on_rescale = lambda devices: app.on_rescale(devices)(s.state)
    s.start()
    s.await_batches(2, timeout=30)
    s.rescale(jax.devices())
    assert app.in_flight == 0
    s.stop()
    assert s.state.shape == (4, 3)


def test_app_publishes_latency_to_bus():
    from repro.elastic.metrics import MetricsBus

    bus = MetricsBus()
    app = StreamingKMeans(n_clusters=4, dim=3, metrics=bus)
    rng = np.random.default_rng(0)
    state = None
    for _ in range(4):
        state = app.process(state, [Msg(rng.normal(size=(200, 3)))])
    app.sync()
    assert bus.value("app.latency_p50", default=-1.0, app="kmeans") >= 0.0
    assert bus.value("app.latency_p99", default=-1.0, app="kmeans") >= 0.0


def test_compile_count_helper():
    f = jax.jit(lambda x: x * 2)
    assert compile_count(f) == 0
    f(jnp.ones((2,)))
    f(jnp.ones((3,)))
    assert compile_count(f) == 2
    assert compile_count(lambda x: x) == -1  # not a jitted fn
