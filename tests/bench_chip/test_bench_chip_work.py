"""The chip benchmark's kinds: work counts against hand counts, and the
plain references against the program's own jnp paths at small sizes (the
references import nothing of the program; only this test does)."""
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip.harness import load_module  # noqa: E402

KINDS = ROOT / "benchmarks" / "chip" / "kinds"
tomo = load_module(KINDS / "tomo.py", "kind")
kmeans = load_module(KINDS / "kmeans.py", "kind")


def tomo_cfg(n_angles=4, n_det=8, n=6, processor="gridrec", iters=2):
    return {"sizes": {"n_angles": n_angles, "n_det": n_det, "pool": 2, "ellipses": 3},
            "stage": {"processor": processor,
                      "options": {"n": n, "mlem_iters": iters, "use_kernel": True}},
            "check": {"recon_rel_l2": 1e-5, "min_frames": 1, "sample": 2}}


def test_tomo_work_hand_count():
    w = tomo.work(tomo_cfg())
    # 4 angles x 36 pixels x 9 ops; sinogram 4x8 in, image 6x6 out, f32
    assert w["bp_flops"] == 4 * 36 * 9 == 1296
    assert w["bp_bytes"] == 4 * (32 + 36) == 272
    # 2 FFTs of 8 (5 N log2 N each) and the complex-by-real multiply, 4 rows
    assert w["gridrec_flops"] == 4 * (2 * 5 * 8 * 3 + 16) + 1296 + 36
    # mlem, 2 iterations: 5 projections and 2 x (ratio on the sinogram, update on the image)
    assert w["mlem_flops"] == 5 * 1296 + 2 * 2 * (32 + 36)


def test_tomo_work_at_the_papers_sizes():
    w = tomo.work({"sizes": {"n_angles": 360, "n_det": 1448},
                   "stage": {"processor": "gridrec", "options": {"n": 1448}}})
    assert w["bp_flops"] == 360 * 1448 ** 2 * 9
    assert w["bp_flops"] / 197e12 > w["bp_bytes"] / 819e9  # operations bind


def test_kmeans_work_hand_count():
    w = kmeans.work({"sizes": {"points_per_msg": 5000, "n_clusters": 10, "dim": 3}})
    assert w["assign_flops"] == 5000 * 10 * (3 * 3 + 1)
    assert w["update_flops"] == 15000
    assert w["assign_bytes"] == 5000 * (12 + 8)


def test_ellipse_projection_keeps_mass():
    ell = np.array([[3.0, -2.0, 9.0, 5.0, 0.4, 0.7]])
    sino = tomo.ellipse_sinogram(ell, 12, 64)
    assert np.allclose(sino.sum(axis=1), math.pi * 9 * 5 * 0.7, rtol=2e-2)  # unit bins over a sqrt profile


def test_stacked_reference_is_framewise():
    cfg = tomo_cfg(n_angles=12, n_det=24, n=16)
    pool = tomo.make_pool(dict(cfg, sizes=dict(cfg["sizes"], pool=3)), 4)
    together = tomo.reference(pool, cfg)
    for sino, ref in zip(pool, together):
        (alone,) = tomo.reference([sino], cfg)
        assert np.allclose(alone, ref, rtol=1e-6, atol=1e-6)


def test_project_is_the_adjoint_of_backproject():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(10, 10)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(7, 16)), jnp.float32)
    angles = jnp.asarray(np.arange(7) * np.pi / 7, jnp.float32)
    lhs = float(jnp.vdot(tomo.project_ref(x, angles, n_det=16), y))
    rhs = float(jnp.vdot(x, tomo.backproject_ref(y[None], angles, n=10)[0]))
    assert lhs == pytest.approx(rhs, rel=1e-5)


@pytest.mark.parametrize("processor", ["gridrec", "mlem"])
def test_tomo_reference_matches_the_program(processor):
    from repro.kernels.tomo import ops

    cfg = tomo_cfg(n_angles=24, n_det=40, n=32, processor=processor)
    sino = tomo.make_pool(cfg, 7)[0]
    angles = jnp.linspace(0, jnp.pi, 24, endpoint=False)
    with jax.default_matmul_precision("highest"):
        if processor == "gridrec":
            prog = ops.gridrec(jnp.asarray(sino), angles, 32)
        else:
            prog = ops.mlem(jnp.asarray(sino), angles, 32, iters=2)
    (ref,) = tomo.reference([sino], cfg)
    assert np.linalg.norm(np.asarray(prog) - ref) / np.linalg.norm(ref) < 1e-5
    # the control, bfloat16 in the data path, is far outside that
    (low,) = tomo.reference([sino], cfg, "bfloat16")
    assert np.linalg.norm(low - ref) / np.linalg.norm(ref) > 1e-3


def test_kmeans_reference_matches_the_program():
    from repro.kernels.kmeans.ops import minibatch_update

    cfg = {"sizes": {"points_per_msg": 500, "dim": 3, "n_clusters": 10, "centre_range": 10.0,
                     "spread": 0.5, "pool": 4}}
    pool = kmeans.make_pool(cfg, 3)
    c0 = kmeans.initial_centroids(cfg, 3)
    # two batches: messages 0 and 2, then 1, 3 and 0
    batches = np.array([[0, 2, -1], [1, 3, 0]], np.int32)
    c = jnp.asarray(c0, jnp.float32)
    prog = []
    with jax.default_matmul_precision("highest"):
        for row in batches:
            pts = np.concatenate([pool[i] for i in row if i >= 0])
            c, _, _ = minibatch_update(jnp.asarray(pts, jnp.float32), c, decay=0.9)
            prog.append(np.asarray(c))
    pool32 = jnp.asarray(np.stack(pool), jnp.float32)
    ref = np.asarray(kmeans.replay(pool32, jnp.asarray(batches), jnp.asarray(c0, jnp.float32), 0.9))
    for p, r in zip(prog, ref):
        assert np.max(np.abs(p - r)) / np.max(np.abs(r)) < 1e-5
    low = np.asarray(kmeans.replay(pool32, jnp.asarray(batches), jnp.asarray(c0, jnp.float32),
                                   0.9, "bfloat16"))
    assert np.max(np.abs(low[-1] - ref[-1])) / np.max(np.abs(ref[-1])) > 1e-3
