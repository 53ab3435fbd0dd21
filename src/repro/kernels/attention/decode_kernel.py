"""Pallas TPU kernel: single-token (decode) attention — the serving hot loop.

Grid: (batch,). Each program holds one sequence's grouped queries
(KV, G, hd) and its whole (S, KV*hd) K/V cache panels in VMEM — the cache's
KV and hd axes are merged into lanes, so the block's last two dims are the
array's own whatever the head count (smollm-135m has KV=3, hd=64). It loops
over the KV heads (a static lane slice each) and runs the online-softmax
recurrence over ``block_kv``-sized cache chunks, early-exiting chunks past
the sequence's live length. ``positions`` arrive by scalar prefetch (SMEM).
With the paged KV cache (repro.serving) the gathered context length is a
small multiple of the page size, so ``block_kv = page_size`` makes chunks
line up with pages and the early exit skips scratch/unwritten pages.

Numerics mirror ``models.attention.decode_attention``: f32 accumulation,
f32-exact matmuls (``Precision.HIGHEST``) and NEG_INF masking of entries
beyond ``positions`` (exact softmax zeros).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *, bkv, skv, hd, g, kv):
    pos = pos_ref[pl.program_id(0)]
    n_needed = jnp.minimum(pos // bkv + 1, skv // bkv)
    hi = jax.lax.Precision.HIGHEST
    for h in range(kv):
        lanes = slice(h * hd, (h + 1) * hd)
        q = q_ref[0, h].astype(jnp.float32) * (1.0 / math.sqrt(hd))  # (G, hd)

        def body(j, carry, lanes=lanes, q=q):
            acc, m, l = carry
            rows = pl.ds(pl.multiple_of(j * bkv, bkv), bkv)
            k_blk = k_ref[0, rows, lanes].astype(jnp.float32)  # (bkv, hd)
            v_blk = v_ref[0, rows, lanes].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())), precision=hi,
                preferred_element_type=jnp.float32)  # (G, bkv)
            k_pos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (g, bkv), 1)
            s = jnp.where(k_pos <= pos, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.sum(axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p, v_blk, (((1,), (0,)), ((), ())), precision=hi,
                preferred_element_type=jnp.float32)
            return acc * alpha + pv, m_new, l

        acc, m, l = jax.lax.fori_loop(
            0, n_needed, body,
            (jnp.zeros((g, hd), jnp.float32), jnp.full((g, 1), NEG_INF, jnp.float32),
             jnp.zeros((g, 1), jnp.float32)))
        o_ref[0, h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_kv", "interpret"))
def decode_attention_pallas(
    q: jax.Array,  # (B, 1, H, hd)
    k_cache: jax.Array,  # (B, S, KV, hd)
    v_cache: jax.Array,
    positions: jax.Array,  # (B,) int32: live length = write index of the new token
    *,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, _, H, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    bkv = min(block_kv, S)
    assert S % bkv == 0, (S, bkv)
    qg = q.reshape(B, KV, G, hd)  # Sq=1 squeezed into the group axis
    kernel = functools.partial(_decode_kernel, bkv=bkv, skv=S, hd=hd, g=G, kv=KV)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, KV, G, hd), lambda b, pos: (b, 0, 0, 0)),
                pl.BlockSpec((1, S, KV * hd), lambda b, pos: (b, 0, 0)),
                pl.BlockSpec((1, S, KV * hd), lambda b, pos: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, KV, G, hd), lambda b, pos: (b, 0, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(positions.astype(jnp.int32), qg,
      k_cache.reshape(B, S, KV * hd), v_cache.reshape(B, S, KV * hd))
    return out.reshape(B, 1, H, hd).astype(v_cache.dtype)
