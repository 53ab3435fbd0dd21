"""Jitted reconstruction ops: GridRec + ML-EM over either backend.

``use_kernel=True`` runs the Pallas TPU projectors, otherwise the jnp
reference. ``interpret`` states the kernel mode; ``None`` derives it in one
place (:func:`repro.kernels.kernel_interpret`): native on a TPU, interpret
mode only where the CPU tests run. GridRec's ramp filter always runs in XLA
(FFT is already optimal there).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import kernel_interpret
from repro.kernels.tomo import ref as R
from repro.kernels.tomo.kernel import backproject_pallas, project_pallas


def _trig(angles):
    a = angles.astype(jnp.float32)
    return jnp.cos(a), jnp.sin(a)


def backproject(sino, angles, n, *, use_kernel=False, interpret=None):
    if not use_kernel:
        return R.backproject_ref(sino, angles, n)
    cos_t, sin_t = _trig(angles)
    return backproject_pallas(sino, cos_t, sin_t, n=n,
                              interpret=kernel_interpret(interpret))


def project(img, angles, n_det, *, use_kernel=False, interpret=None):
    if not use_kernel:
        return R.project_ref(img, angles, n_det)
    cos_t, sin_t = _trig(angles)
    return project_pallas(img, cos_t, sin_t, n_det=n_det,
                          interpret=kernel_interpret(interpret))


def gridrec(sino, angles, n, *, window="ramlak", use_kernel=False, interpret=None):
    """FFT filtered backprojection (paper's fast reconstruction)."""
    filtered = R.ramp_filter(sino, window=window)
    bp = backproject(filtered, angles, n, use_kernel=use_kernel, interpret=interpret)
    return bp * (jnp.pi / (2.0 * angles.shape[0]))


def mlem(sino, angles, n, *, iters=8, use_kernel=False, interpret=None):
    """Iterative ML-EM (paper's high-fidelity reconstruction)."""
    n_det = sino.shape[1]
    eps = 1e-6
    norm = backproject(jnp.ones_like(sino), angles, n, use_kernel=use_kernel, interpret=interpret) + eps

    def body(x, _):
        fp = project(x, angles, n_det, use_kernel=use_kernel, interpret=interpret)
        ratio = sino / jnp.maximum(fp, eps)
        bp = backproject(ratio, angles, n, use_kernel=use_kernel, interpret=interpret)
        return x * bp / norm, None

    x0 = jnp.ones((n, n), jnp.float32)
    x, _ = jax.lax.scan(body, x0, None, length=iters)
    return x


def _backproject_batch(sinos, angles, n, *, use_kernel, interpret):
    if not use_kernel:
        # hand-batched ref (ref.py): vmapping the scalar path de-fuses the
        # per-angle weight construction and runs ~4x slower
        return R.backproject_ref_batch(sinos, angles, n)
    fn = functools.partial(backproject, n=n, use_kernel=True, interpret=interpret)
    return jax.vmap(fn, in_axes=(0, None))(sinos, angles)


def _project_batch(imgs, angles, n_det, *, use_kernel, interpret):
    if not use_kernel:
        return R.project_ref_batch(imgs, angles, n_det)
    fn = functools.partial(project, n_det=n_det, use_kernel=True, interpret=interpret)
    return jax.vmap(fn, in_axes=(0, None))(imgs, angles)


def gridrec_batch(sinos, angles, n, *, window="ramlak", use_kernel=False, interpret=None):
    """Stacked GridRec over a (B, A, n_det) sinogram micro-batch — one fused
    call instead of a per-message Python loop (the streaming hot path)."""
    filtered = R.ramp_filter(sinos, window=window)  # filters along axis -1
    bp = _backproject_batch(filtered, angles, n, use_kernel=use_kernel, interpret=interpret)
    return bp * (jnp.pi / (2.0 * angles.shape[0]))


def mlem_batch(sinos, angles, n, *, iters=8, use_kernel=False, interpret=None):
    """Stacked ML-EM over a (B, A, n_det) sinogram micro-batch."""
    b, _, n_det = sinos.shape
    eps = 1e-6
    norm = _backproject_batch(jnp.ones_like(sinos), angles, n,
                              use_kernel=use_kernel, interpret=interpret) + eps

    def body(x, _):
        fp = _project_batch(x, angles, n_det, use_kernel=use_kernel, interpret=interpret)
        ratio = sinos / jnp.maximum(fp, eps)
        bp = _backproject_batch(ratio, angles, n, use_kernel=use_kernel, interpret=interpret)
        return x * bp / norm, None

    x0 = jnp.ones((b, n, n), jnp.float32)
    x, _ = jax.lax.scan(body, x0, None, length=iters)
    return x


def shepp_logan(n: int) -> jnp.ndarray:
    """Tiny synthetic phantom (sum of ellipses) for tests/benchmarks."""
    y, x = jnp.mgrid[0:n, 0:n]
    cx = cy = (n - 1) / 2.0
    xn, yn = (x - cx) / (n / 2), (y - cy) / (n / 2)
    img = jnp.zeros((n, n), jnp.float32)
    for (a, b, x0, y0, val) in [
        (0.69, 0.92, 0.0, 0.0, 1.0),
        (0.66, 0.87, 0.0, -0.02, -0.8),
        (0.11, 0.31, 0.22, 0.0, -0.2),
        (0.16, 0.41, -0.22, 0.0, -0.2),
        (0.21, 0.25, 0.0, 0.35, 0.1),
        (0.046, 0.046, 0.0, 0.1, 0.1),
    ]:
        mask = ((xn - x0) / a) ** 2 + ((yn - y0) / b) ** 2 <= 1.0
        img = img + val * mask
    return jnp.clip(img, 0.0, None)
