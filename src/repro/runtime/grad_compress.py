"""Quantized cross-pod gradient reduction (beyond-paper distributed-opt trick).

The inter-pod link is the scarcest bandwidth on a multi-pod system (DCI <<
ICI). Gradients are reduced hierarchically: full-precision reduce-scatter
inside the pod (GSPMD), then an **int8 block-quantized all-gather + local
sum** across the "pod" axis via shard_map, with error feedback carrying the
quantization residual into the next step (Seide et al. / 1-bit-Adam lineage).

Why all-gather instead of all-reduce: an int8 all-reduce would overflow (or
silently upcast to int32 on the wire); gathering the int8 payloads + per-
block scales and summing after dequantization keeps the wire format at
~1.02 B/param vs 4 B/param f32 — a ~3.9x cross-pod traffic cut, visible as
`all-gather s8[...]` in the compiled HLO.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

BLOCK = 256


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-block int8 quantization. x: flat (N,) f32, N % BLOCK == 0."""
    blocks = x.reshape(-1, BLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(blocks / jnp.maximum(scale, 1e-30)), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array, shape) -> jax.Array:
    return (q.astype(jnp.float32) * scale).reshape(shape)


def _pad_flat(x: jax.Array) -> jax.Array:
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % BLOCK
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    return flat


def quantized_psum(x: jax.Array, resid: jax.Array, axis_name: str = "pod") -> tuple[jax.Array, jax.Array]:
    """Quantized cross-pod sum — call INSIDE a shard_map that is manual over
    ``axis_name``. ``x``: this pod's partial gradient (any shape); ``resid``:
    flat error-feedback state (padded length, see ``resid_len``).

    Returns (reduced value with x's shape/dtype, new residual).
    """
    shape, dtype = x.shape, x.dtype
    flat = _pad_flat(x)
    corrected = flat + resid
    q, scale = quantize_int8(corrected)
    deq = dequantize_int8(q, scale, corrected.shape)
    new_resid = corrected - deq  # error feedback: residual re-enters next step
    qg = jax.lax.all_gather(q, axis_name)  # (p, blocks, BLOCK) int8 on the wire
    sg = jax.lax.all_gather(scale, axis_name)  # (p, blocks, 1) f32 (tiny)
    reduced = jnp.sum(qg.astype(jnp.float32) * sg, axis=0).reshape(flat.shape)
    n = 1
    for d in shape:
        n *= d
    return reduced[:n].reshape(shape).astype(dtype), new_resid


def resid_len(n_params: int) -> int:
    """Length of the flat error-feedback buffer for an ``n_params`` leaf."""
    return ((n_params + BLOCK - 1) // BLOCK) * BLOCK


def quantized_psum_tree(grads: Any, resids: Any, axis_name: str = "pod") -> tuple[Any, Any]:
    """Tree version of :func:`quantized_psum` (still inside a shard_map)."""
    flat_g, treedef = jax.tree.flatten(grads)
    flat_r = jax.tree.leaves(resids)
    outs = [quantized_psum(g, r, axis_name) for g, r in zip(flat_g, flat_r)]
    return (
        jax.tree.unflatten(treedef, [o[0] for o in outs]),
        jax.tree.unflatten(treedef, [o[1] for o in outs]),
    )


def compression_wire_bytes(n_params: int) -> tuple[int, int]:
    """(compressed, f32) bytes per cross-pod exchange of one gradient copy."""
    blocks = (n_params + BLOCK - 1) // BLOCK
    return n_params * 1 + blocks * 4, n_params * 4
