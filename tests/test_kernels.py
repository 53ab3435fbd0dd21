"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attention import attention_ref, flash_attention
from repro.kernels.kmeans import assign, assign_ref, minibatch_update
from repro.kernels.tomo import (
    backproject,
    backproject_ref,
    gridrec,
    mlem,
    project,
    project_ref,
    shepp_logan,
)

# ---------------------------------------------------------------------------
# kmeans
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,d,k", [(64, 4, 3), (300, 7, 5), (128, 128, 16), (97, 3, 10)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kmeans_assign_kernel_matches_ref(n, d, k, dtype):
    key = jax.random.key(n + d + k)
    pts = jax.random.normal(key, (n, d), jnp.float32).astype(dtype)
    cen = jax.random.normal(jax.random.key(1), (k, d), jnp.float32).astype(dtype)
    l_ref, d_ref = assign_ref(pts, cen)
    l_k, d_k = assign(pts, cen, use_kernel=True, block_n=64, interpret=True)
    assert bool((l_ref == l_k).all())
    np.testing.assert_allclose(np.asarray(d_ref), np.asarray(d_k), rtol=2e-2, atol=2e-2)


def test_kmeans_minibatch_update_converges():
    rng = np.random.default_rng(0)
    centers = np.array([[-5.0, 0.0], [5.0, 0.0], [0.0, 5.0]])

    def batch():
        return jnp.asarray(
            centers[rng.integers(0, 3, 256)] + rng.normal(0, 0.3, (256, 2)), jnp.float32
        )

    # farthest-point (kmeans++-style) seeding avoids the two-centroids-one-
    # cluster local minimum; the test verifies the *update math* converges
    pts0 = np.asarray(batch())
    seeds = [pts0[0]]
    for _ in range(2):
        d = np.min([np.sum((pts0 - s) ** 2, axis=1) for s in seeds], axis=0)
        seeds.append(pts0[int(np.argmax(d))])
    cen = jnp.asarray(np.stack(seeds), jnp.float32)
    inertia_hist = []
    for i in range(20):
        cen, _, inertia = minibatch_update(batch(), cen, decay=0.6)
        inertia_hist.append(float(inertia) / 256)
    assert inertia_hist[-1] < inertia_hist[0] / 2
    assert inertia_hist[-1] < 2.0  # near the true within-cluster variance


# ---------------------------------------------------------------------------
# tomo
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,n_det,a", [(16, 24, 8), (32, 48, 16), (32, 32, 24)])
def test_tomo_projectors_match_ref(n, n_det, a):
    img = shepp_logan(n)
    angles = jnp.linspace(0, jnp.pi, a, endpoint=False)
    np.testing.assert_allclose(
        np.asarray(project(img, angles, n_det, use_kernel=True, interpret=True)),
        np.asarray(project_ref(img, angles, n_det)),
        atol=1e-4,
    )
    sino = project_ref(img, angles, n_det)
    np.testing.assert_allclose(
        np.asarray(backproject(sino, angles, n, use_kernel=True, interpret=True)),
        np.asarray(backproject_ref(sino, angles, n)),
        atol=1e-3,
    )


_QUARTERS = [0.0, np.pi / 4, np.pi / 2, 3 * np.pi / 4]


@pytest.mark.parametrize(
    "n,n_det,angles,batch",
    [
        (40, 40, np.linspace(0, np.pi, 10, endpoint=False), None),
        (72, 64, np.linspace(0, np.pi, 12, endpoint=False), None),
        # pixels beyond both detector ends: their taps are dropped
        (96, 48, np.linspace(0, np.pi, 9, endpoint=False), None),
        (48, 56, _QUARTERS, None),
        (40, 48, np.linspace(0, np.pi, 7, endpoint=False), 3),
    ],
    ids=["n40", "n72", "n_past_detector", "quarter_angles", "stack3"],
)
def test_tomo_backproject_kernel_matches_ref(n, n_det, angles, batch):
    angles = jnp.asarray(angles, jnp.float32)
    shape = (angles.shape[0], n_det) if batch is None else (batch, angles.shape[0], n_det)
    sino = jax.random.normal(jax.random.key(n + n_det), shape, jnp.float32)
    if batch is None:
        got = backproject(sino, angles, n, use_kernel=True, interpret=True)
        want = backproject_ref(sino, angles, n)
    else:
        got = jax.vmap(lambda s: backproject(s, angles, n, use_kernel=True, interpret=True))(sino)
        want = jnp.stack([backproject_ref(s, angles, n) for s in sino])
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


def test_tomo_backproject_kernel_is_the_adjoint_of_project_ref():
    """ML-EM pairs the kernel's backprojection with the forward projector:
    <P x, y> = <x, B y>. Positive inputs and float64 dot products, so the
    only rounding is the projectors' own."""
    n, n_det, a = 40, 48, 12
    angles = jnp.linspace(0, jnp.pi, a, endpoint=False)
    x = jax.random.uniform(jax.random.key(2), (n, n))
    y = jax.random.uniform(jax.random.key(3), (a, n_det))
    px = np.asarray(project_ref(x, angles, n_det), np.float64)
    by = np.asarray(backproject(y, angles, n, use_kernel=True, interpret=True), np.float64)
    lhs = np.vdot(px, np.asarray(y, np.float64))
    rhs = np.vdot(np.asarray(x, np.float64), by)
    np.testing.assert_allclose(rhs, lhs, rtol=1e-5)


def test_tomo_projectors_are_adjoint():
    n, n_det, a = 24, 32, 12
    angles = jnp.linspace(0, jnp.pi, a, endpoint=False)
    x = jax.random.normal(jax.random.key(0), (n, n))
    y = jax.random.normal(jax.random.key(1), (a, n_det))
    lhs = jnp.vdot(project_ref(x, angles, n_det), y)
    rhs = jnp.vdot(x, backproject_ref(y, angles, n))
    np.testing.assert_allclose(float(lhs), float(rhs), rtol=1e-4)


def test_reconstruction_quality_ordering():
    """Paper §6.4: ML-EM (iterative) reconstructs with better fidelity than
    GridRec; GridRec is the cheaper algorithm."""
    n, a = 48, 60
    img = shepp_logan(n)
    angles = jnp.linspace(0, jnp.pi, a, endpoint=False)
    sino = project_ref(img, angles, n + 16)

    def err(rec):
        return float(jnp.sqrt(jnp.mean((rec - img) ** 2)))

    e_grid = err(gridrec(sino, angles, n))
    e_mlem = err(mlem(sino, angles, n, iters=16))
    assert e_mlem < e_grid
    assert e_mlem < 0.5 * float(jnp.sqrt(jnp.mean(img**2)))


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,KV,hd", [(1, 32, 2, 1, 16), (2, 64, 4, 2, 32), (1, 48, 6, 3, 8)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_kernel_matches_ref(B, S, H, KV, hd, causal):
    ks = jax.random.split(jax.random.key(B * S), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    out_k = flash_attention(q, k, v, causal=causal, block_q=16, block_kv=16,
                            use_kernel=True, interpret=True)
    out_r = flash_attention(q, k, v, causal=causal, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel_dtypes(dtype):
    B, S, H, KV, hd = 1, 32, 2, 2, 16
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd)).astype(dtype)
    out_k = flash_attention(q, k, v, block_q=16, block_kv=16, use_kernel=True, interpret=True)
    out_r = flash_attention(q, k, v, use_kernel=False)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32), atol=tol
    )


def test_sharded_flash_custom_vjp_grads_match_naive():
    """The distributed train-path flash (runtime/sharded_attention.py) must
    produce exact gradients — it is used inside every train step."""
    from repro.models.attention import naive_attention
    from repro.runtime.sharded_attention import flash_attention as flash_vjp

    B, S, H, KV, hd = 2, 64, 4, 2, 16
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    q_pos = jnp.arange(S, dtype=jnp.float32)

    def loss_flash(q, k, v):
        o = flash_vjp(q.reshape(B, S, KV, H // KV, hd), k, v, q_pos, True, 16, hd**-0.5)
        return jnp.sum(jnp.sin(o))

    def loss_naive(q, k, v):
        return jnp.sum(jnp.sin(naive_attention(q, k, v, causal=True)))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
