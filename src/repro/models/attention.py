"""Attention: GQA with blockwise (flash) / naive / ring backends + KV cache.

Distribution scheme: heads are never sharded — Q/K/V
activations are *sequence*-sharded on the "model" mesh axis, which removes
every head-count divisibility constraint of the assigned pool (9/24/40 heads,
kv=2/3/8 on a 16-way axis). Blockwise attention keeps the O(block) memory
profile of flash attention in pure JAX so it lowers on any backend; the
Pallas TPU kernel (kernels/attention) is the hardware target for prefill and
is numerically validated against the same reference.

All functions take Q: (B, Sq, H, hd); K,V: (B, Skv, KV, hd) with H % KV == 0.
"""
from __future__ import annotations

import contextlib
import functools
import math
import threading
from typing import Literal

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# decode-kernel routing context (see decode_kernel_scope)
_DECODE_KERNEL = threading.local()


def _group(q: jax.Array, n_kv: int) -> jax.Array:
    """(B,S,H,hd) -> (B,S,KV,G,hd) grouped by kv head."""
    B, S, H, hd = q.shape
    return q.reshape(B, S, n_kv, H // n_kv, hd)


def naive_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_positions: jax.Array | None = None,
    kv_positions: jax.Array | None = None,
    kv_mask: jax.Array | None = None,
) -> jax.Array:
    """Reference attention; materializes full scores. Oracle + small shapes."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    qg = _group(q, KV)  # (B,Sq,KV,G,hd)
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if q_positions is None:
        q_positions = jnp.arange(Sq)[None]
    if kv_positions is None:
        kv_positions = jnp.arange(k.shape[1])[None]
    mask = jnp.ones((B, Sq, k.shape[1]), bool)
    if causal:
        mask &= q_positions[:, :, None] >= kv_positions[:, None, :]
    if kv_mask is not None:
        mask &= kv_mask[:, None, :]
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    return out.reshape(B, Sq, H, hd)


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 512,
    block_kv: int = 1024,
) -> jax.Array:
    """Flash-style attention in pure JAX: online softmax over KV blocks.

    Peak memory is O(block_q * block_kv) per (batch, kv-head, group) instead
    of O(Sq * Skv). Under GSPMD with Q sequence-sharded this is the baseline
    production attention; the Pallas kernel implements the same schedule in
    VMEM on TPU.
    """
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0, (Sq, block_q, Skv, block_kv)
    nq, nk = Sq // block_q, Skv // block_kv
    scale = 1.0 / math.sqrt(hd)

    qg = _group(q, KV).astype(jnp.float32) * scale  # (B,Sq,KV,G,hd)
    qg = qg.reshape(B, nq, block_q, KV, H // KV, hd)
    kb = k.reshape(B, nk, block_kv, KV, hd)
    vb = v.reshape(B, nk, block_kv, KV, hd)

    q_pos = jnp.arange(Sq).reshape(nq, block_q)
    k_pos = jnp.arange(Skv).reshape(nk, block_kv)

    def q_block(args):
        qi, qp = args  # (B,bq,KV,G,hd), (bq,)

        def kv_step(carry, kv_args):
            acc, m, l = carry
            ki, vi, kp = kv_args  # (B,bkv,KV,hd), ..., (bkv,)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qi, ki.astype(jnp.float32))
            if causal:
                s = jnp.where(qp[:, None] >= kp[None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum("bkgqs,bskd->bkgqd", p, vi.astype(jnp.float32))
            return (acc, m_new, l), None

        G = qi.shape[3]
        acc0 = jnp.zeros((B, KV, G, qi.shape[1], hd), jnp.float32)
        m0 = jnp.full((B, KV, G, qi.shape[1]), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, qi.shape[1]), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0), (kb.swapaxes(0, 1), vb.swapaxes(0, 1), k_pos))
        out = acc / jnp.maximum(l[..., None], 1e-30)  # (B,KV,G,bq,hd)
        return out.transpose(0, 3, 1, 2, 4)  # (B,bq,KV,G,hd)

    out = jax.lax.map(q_block, (qg.swapaxes(0, 1), q_pos))  # (nq,B,bq,KV,G,hd)
    out = out.swapaxes(0, 1).reshape(B, Sq, H, hd)
    return out.astype(v.dtype)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    *,
    positions: jax.Array,
) -> jax.Array:
    """One-token attention against a (possibly sequence-sharded) KV cache.

    ``q``: (B, 1, H, hd); caches: (B, S, KV, hd); ``positions``: (B,) number
    of valid cache entries per sequence (the new token attends to < pos+1).
    Softmax reductions over the sharded S dim lower to partial max/sum +
    all-reduce under GSPMD — a distributed flash-decode by construction.

    Inside a :func:`decode_kernel_scope` the same computation dispatches to
    the Pallas decode kernel (kernels/attention/decode_kernel.py) — routing
    happens at trace time, so a jitted decode step traced under the scope
    bakes the kernel in.
    """
    cfg = getattr(_DECODE_KERNEL, "cfg", None)
    if cfg is not None:
        from repro.kernels.attention.decode_kernel import decode_attention_pallas

        return decode_attention_pallas(q, k_cache, v_cache, positions, **cfg)
    B, _, H, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    qg = _group(q, KV)[:, 0].astype(jnp.float32)  # (B,KV,G,hd) after squeeze
    qg = qg * (1.0 / math.sqrt(hd))
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache.astype(jnp.float32))
    valid = jnp.arange(S)[None, :] <= positions[:, None]  # (B,S)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, hd).astype(v_cache.dtype)


@contextlib.contextmanager
def decode_kernel_scope(*, block_kv: int = 128, interpret: bool | None = None):
    """Route :func:`decode_attention` through the Pallas decode kernel.

    Trace-time routing: wrap the *tracing* call (the first invocation of a
    jitted decode step) — the traced HLO then contains the kernel for the
    life of that compilation. ``interpret=None`` derives the mode in one
    place (:func:`repro.kernels.kernel_interpret`): native on a TPU,
    interpret mode only where the CPU tests run.
    """
    from repro.kernels import kernel_interpret

    prev = getattr(_DECODE_KERNEL, "cfg", None)
    _DECODE_KERNEL.cfg = {"block_kv": int(block_kv),
                          "interpret": kernel_interpret(interpret)}
    try:
        yield
    finally:
        _DECODE_KERNEL.cfg = prev


def update_cache(
    cache: jax.Array, new: jax.Array, positions: jax.Array
) -> jax.Array:
    """Write ``new`` (B,1,KV,hd) into ``cache`` (B,S,KV,hd) at per-seq ``positions``.

    Implemented as a scatter (per-sequence write offsets -> continuous
    batching); lowers to a guarded local update per shard when S is sharded.
    """
    B = cache.shape[0]
    return cache.at[jnp.arange(B), positions].set(new[:, 0].astype(cache.dtype))


ATTENTION_IMPLS = {
    "naive": naive_attention,
    "blockwise": blockwise_attention,
}


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    impl: str = "blockwise",
    causal: bool = True,
    block_q: int = 512,
    block_kv: int = 1024,
) -> jax.Array:
    # Under an activation_rules context with a real "model" axis, train/prefill
    # attention runs sequence-parallel via shard_map (see
    # runtime/sharded_attention.py for why GSPMD alone can't do this well).
    from repro.runtime.sharding import _CTX  # lazy to avoid cycle

    rules = getattr(_CTX, "rules", None)
    if rules is not None and rules.mesh.shape.get("model", 1) > 1:
        n_model = rules.mesh.shape["model"]
        if q.shape[1] % n_model == 0 and k.shape[1] % n_model == 0 and q.shape[1] > 1:
            from repro.runtime.sharded_attention import sharded_attention

            shard_impl = {"ring": "ring", "flash": "flash"}.get(impl, "allgather")
            return sharded_attention(
                q, k, v, rules, causal=causal, block_kv=block_kv, impl=shard_impl
            )
    if impl == "naive":
        return naive_attention(q, k, v, causal=causal)
    if impl in ("blockwise", "ring", "flash"):
        return blockwise_attention(q, k, v, causal=causal, block_q=block_q, block_kv=block_kv)
    raise ValueError(f"unknown attention impl {impl!r}")
