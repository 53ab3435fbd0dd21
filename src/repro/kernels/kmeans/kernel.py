"""Pallas TPU kernel: K-Means assignment (tiled distance matrix + argmin).

Tiling: grid over point blocks; each program loads a (BN, D) point tile and
the full (K, D) centroid set into VMEM, computes the distance tile with an
MXU matmul (-2 * P @ C^T) and reduces the argmin in-register (VPU). K and D
are padded to lane multiples by ``ops.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _assign_kernel(p_ref, c_ref, c2_ref, labels_ref, dist_ref):
    p = p_ref[...].astype(jnp.float32)  # (BN, D)
    c = c_ref[...].astype(jnp.float32)  # (K, D)
    c2 = c2_ref[...]  # (1, K)
    cross = jax.lax.dot_general(
        p, c, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (BN, K) on the MXU, f32-exact (a single bf16 pass would move labels)
    p2 = jnp.sum(p * p, axis=1, keepdims=True)  # (BN, 1)
    d2 = p2 - 2.0 * cross + c2  # (BN, K)
    labels_ref[...] = jnp.argmin(d2, axis=1).astype(jnp.int32)
    dist_ref[...] = jnp.min(d2, axis=1)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def assign_pallas(
    points: jax.Array,
    centroids: jax.Array,
    *,
    block_n: int = 1024,
    interpret: bool = False,
):
    """points: (N, D); centroids: (K, D). N % block_n == 0 (ops.py pads)."""
    n, d = points.shape
    k = centroids.shape[0]
    block_n = min(block_n, n)
    assert n % block_n == 0, (n, block_n)
    c2 = jnp.sum(centroids.astype(jnp.float32) ** 2, axis=1)[None, :]  # (1, K)

    return pl.pallas_call(
        _assign_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),  # point tile -> VMEM
            pl.BlockSpec((k, d), lambda i: (0, 0)),  # centroids -> VMEM (all tiles)
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
        ],
        interpret=interpret,
    )(points, centroids, c2)
