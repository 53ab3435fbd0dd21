"""Jitted wrapper: padding + kernel/ref dispatch for K-Means assignment."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import kernel_interpret
from repro.kernels.kmeans.kernel import assign_pallas
from repro.kernels.kmeans.ref import assign_ref, update_ref, update_scatter


def _pad_to(x: jax.Array, m: int, axis: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def assign(points, centroids, *, use_kernel: bool = False, block_n: int = 1024,
           interpret: bool | None = None):
    """K-Means assignment. ``use_kernel`` selects the Pallas TPU kernel,
    otherwise the jnp reference (which XLA also fuses well). ``interpret``
    states the kernel mode; ``None`` derives it in one place
    (:func:`repro.kernels.kernel_interpret`): native on a TPU, interpret
    mode only where the CPU tests run."""
    n, d = points.shape
    k = centroids.shape[0]
    if not use_kernel:
        return assign_ref(points, centroids)
    # pad: lanes want multiples of 128 on D and K; block on N
    pp = _pad_to(_pad_to(points, 128, 1), min(block_n, 1024), 0)
    cp = _pad_to(_pad_to(centroids, 128, 1), 8, 0)
    kp = cp.shape[0]
    if kp > k:  # padded centroids must never win the argmin
        cp = cp.at[k:].set(1e30)
    labels, dist = assign_pallas(pp, cp, block_n=min(block_n, pp.shape[0]),
                                 interpret=kernel_interpret(interpret))
    return labels[:n], dist[:n]


def minibatch_update(points, centroids, *, decay: float = 0.9, use_kernel: bool = False, interpret: bool | None = None):
    """One streaming K-Means step: assign + decayed centroid update
    (paper §3.2.1 "averaging using a decay factor")."""
    k = centroids.shape[0]
    labels, dist = assign(points, centroids, use_kernel=use_kernel, interpret=interpret)
    sums, counts = update_scatter(points, labels, k)
    batch_means = sums / jnp.maximum(counts[:, None], 1.0)
    seen = (counts > 0)[:, None]
    new_centroids = jnp.where(
        seen, decay * centroids + (1.0 - decay) * batch_means, centroids
    )
    inertia = dist.sum()
    return new_centroids.astype(centroids.dtype), labels, inertia


def minibatch_update_masked(points, centroids, n_valid, *, decay: float = 0.9,
                            use_kernel: bool = False, interpret: bool | None = None):
    """Bucket-padded streaming step: rows ``>= n_valid`` are zero padding and
    contribute nothing to the update or the inertia.

    This is the shape-bucketed hot-path entry: a jitted wrapper compiles once
    per *bucket* shape while ``n_valid`` stays a dynamic scalar, so variable
    batch sizes reuse the same executable. Centroids are bit-identical to
    :func:`minibatch_update` on the unpadded batch (padding rows carry exact
    zero weight in every accumulation). Padding rows get label ``-1``.
    """
    k = centroids.shape[0]
    labels, dist = assign(points, centroids, use_kernel=use_kernel, interpret=interpret)
    mask = jnp.arange(points.shape[0]) < n_valid
    sums, counts = update_scatter(points, labels, k, mask=mask)
    batch_means = sums / jnp.maximum(counts[:, None], 1.0)
    seen = (counts > 0)[:, None]
    new_centroids = jnp.where(
        seen, decay * centroids + (1.0 - decay) * batch_means, centroids
    )
    inertia = jnp.where(mask, dist, 0.0).sum()
    labels = jnp.where(mask, labels, -1)
    return new_centroids.astype(centroids.dtype), labels, inertia
