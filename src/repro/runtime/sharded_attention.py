"""shard_map attention: sequence-parallel flash attention for train/prefill.

Why not plain GSPMD: a flash-style q-block loop is a *sequential* construct;
under GSPMD with the sequence dim sharded, reshaping (S,) -> (nq, bq) forces
an all-gather and the loop serializes across shards (measured: ~390 GB/device
collectives on smollm training at 4k tokens). The SPMD-correct structure maps the q-block
loop onto the mesh: each "model" shard owns S/16 query rows and runs a local
online-softmax loop over KV blocks.

Baseline schedule: all-gather K,V over "model" (one fused collective per
layer), then a dynamic-bound fori_loop over KV blocks with causal early-exit
(shard i stops after (i+1) * S_local rows). The ring schedule (§Perf,
runtime/ring_attention.py) replaces the all-gather with overlapped
collective-permutes.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _pick_block(s: int, want: int) -> int:
    b = min(want, s)
    while s % b:
        b -= 1
    return b


def local_flash(q, k, v, *, q_offset, causal: bool, block_kv: int, scale: float,
                differentiable: bool):
    """Per-device flash attention.

    q: (B, Sq, KV, G, hd) grouped queries (global row ``q_offset + i``);
    k, v: (B, Skv, KV, hd) full keys/values. Online softmax over KV blocks.

    ``differentiable=False`` (prefill): dynamic-bound fori_loop — a causal
    shard skips KV blocks beyond its last query row (dynamic trip count is
    fine forward-only). ``differentiable=True`` (train): static lax.scan over
    all blocks with masking — reverse-mode AD cannot differentiate a
    dynamic-trip while loop. The §Perf pass replaces the train path with a
    custom-VJP flash that restores the causal skip.
    """
    B, Sq, KV, G, hd = q.shape
    Skv = k.shape[1]
    bkv = _pick_block(Skv, block_kv)
    n_blocks = Skv // bkv
    qf = q.astype(jnp.float32) * scale
    q_pos = q_offset + jnp.arange(Sq)  # global rows

    acc0 = jnp.zeros((B, KV, G, Sq, hd), jnp.float32)
    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)

    def attend(carry, j, k_blk, v_blk):
        acc, m, l = carry
        k_blk = k_blk.astype(jnp.float32)
        v_blk = v_blk.astype(jnp.float32)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qf, k_blk)
        if causal:
            k_pos = j * bkv + jnp.arange(bkv)
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bkgqs,bskd->bkgqd", p, v_blk)
        return acc, m_new, l

    if differentiable:
        kb = k.reshape(B, n_blocks, bkv, KV, hd).swapaxes(0, 1)
        vb = v.reshape(B, n_blocks, bkv, KV, hd).swapaxes(0, 1)

        def step(carry, inp):
            j, k_blk, v_blk = inp
            return attend(carry, j, k_blk, v_blk), None

        (acc, m, l), _ = jax.lax.scan(
            step, (acc0, m0, l0), (jnp.arange(n_blocks), kb, vb)
        )
    else:
        def body(j, carry):
            k_blk = jax.lax.dynamic_slice_in_dim(k, j * bkv, bkv, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, j * bkv, bkv, axis=1)
            return attend(carry, j, k_blk, v_blk)

        if causal:  # shard only needs KV rows <= its last query row
            n_needed = jnp.minimum((q_offset + Sq + bkv - 1) // bkv, n_blocks)
        else:
            n_needed = n_blocks
        acc, m, l = jax.lax.fori_loop(0, n_needed, body, (acc0, m0, l0))

    out = acc / jnp.maximum(l[..., None], 1e-30)  # (B,KV,G,Sq,hd)
    return out.transpose(0, 3, 1, 2, 4)  # (B,Sq,KV,G,hd)


# ---------------------------------------------------------------------------
# custom-VJP flash: no per-block residuals saved (bwd recomputes each block),
# causal early-exit in both directions. This is what bounds train-time
# attention memory to O(block) and halves causal attention flops vs the
# masked static scan.
# ---------------------------------------------------------------------------


def _flash_fwd_core(q, k, v, q_pos, *, causal, block_kv, scale):
    """Returns (out f32 (B,KV,G,Sq,hd), lse (B,KV,G,Sq)).

    ``q_pos``: (Sq,) f32 global row positions (f32 so it can be a plain
    differentiable arg of the custom_vjp with a zero cotangent — it is traced
    per-shard via axis_index and hence cannot be a nondiff argnum).
    """
    B, Sq, KV, G, hd = q.shape
    Skv = k.shape[1]
    bkv = _pick_block(Skv, block_kv)
    n_blocks = Skv // bkv
    qf = q.astype(jnp.float32) * scale

    acc0 = jnp.zeros((B, KV, G, Sq, hd), jnp.float32)
    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)

    def body(j, carry):
        acc, m, l = carry
        k_blk = jax.lax.dynamic_slice_in_dim(k, j * bkv, bkv, axis=1).astype(jnp.float32)
        v_blk = jax.lax.dynamic_slice_in_dim(v, j * bkv, bkv, axis=1).astype(jnp.float32)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qf, k_blk)
        if causal:
            k_pos = (j * bkv + jnp.arange(bkv)).astype(jnp.float32)
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bkgqs,bskd->bkgqd", p, v_blk)
        return acc, m_new, l

    if causal:  # shard needs KV blocks up to its last query row only
        n_needed = jnp.minimum(q_pos[-1].astype(jnp.int32) // bkv + 1, n_blocks)
    else:
        n_needed = n_blocks
    acc, m, l = jax.lax.fori_loop(0, n_needed, body, (acc0, m0, l0))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out, lse


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention(q, k, v, q_pos, causal, block_kv, scale):
    out, _ = _flash_fwd_core(q, k, v, q_pos, causal=causal, block_kv=block_kv, scale=scale)
    return out.transpose(0, 3, 1, 2, 4).astype(v.dtype)  # (B,Sq,KV,G,hd)


def _flash_fwd(q, k, v, q_pos, causal, block_kv, scale):
    out, lse = _flash_fwd_core(q, k, v, q_pos, causal=causal, block_kv=block_kv, scale=scale)
    res = (q, k, v, q_pos, out, lse)
    return out.transpose(0, 3, 1, 2, 4).astype(v.dtype), res


def _flash_bwd(causal, block_kv, scale, res, g):
    q, k, v, q_pos, out, lse = res  # out/lse f32 (B,KV,G,Sq,...)
    B, Sq, KV, G, hd = q.shape
    Skv = k.shape[1]
    bkv = _pick_block(Skv, block_kv)
    n_blocks = Skv // bkv
    qf = q.astype(jnp.float32) * scale
    do = g.transpose(0, 2, 3, 1, 4).astype(jnp.float32)  # (B,KV,G,Sq,hd)
    delta = jnp.sum(do * out, axis=-1)  # (B,KV,G,Sq)

    dq0 = jnp.zeros((B, KV, G, Sq, hd), jnp.float32)
    dk0 = jnp.zeros((B, Skv, KV, hd), jnp.float32)
    dv0 = jnp.zeros((B, Skv, KV, hd), jnp.float32)

    def body(j, carry):
        dq, dk, dv = carry
        k_blk = jax.lax.dynamic_slice_in_dim(k, j * bkv, bkv, axis=1).astype(jnp.float32)
        v_blk = jax.lax.dynamic_slice_in_dim(v, j * bkv, bkv, axis=1).astype(jnp.float32)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qf, k_blk)
        if causal:
            k_pos = (j * bkv + jnp.arange(bkv)).astype(jnp.float32)
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])  # (B,KV,G,Sq,bkv)
        dv_blk = jnp.einsum("bkgqs,bkgqd->bskd", p, do)
        dp = jnp.einsum("bkgqd,bskd->bkgqs", do, v_blk)
        ds = p * (dp - delta[..., None])  # d(s_scaled)
        dq = dq + jnp.einsum("bkgqs,bskd->bkgqd", ds, k_blk) * scale
        dk_blk = jnp.einsum("bkgqs,bqkgd->bskd", ds, q.astype(jnp.float32)) * scale
        dk = jax.lax.dynamic_update_slice_in_dim(
            dk, jax.lax.dynamic_slice_in_dim(dk, j * bkv, bkv, 1) + dk_blk, j * bkv, 1
        )
        dv = jax.lax.dynamic_update_slice_in_dim(
            dv, jax.lax.dynamic_slice_in_dim(dv, j * bkv, bkv, 1) + dv_blk, j * bkv, 1
        )
        return dq, dk, dv

    if causal:
        n_needed = jnp.minimum(q_pos[-1].astype(jnp.int32) // bkv + 1, n_blocks)
    else:
        n_needed = n_blocks
    dq, dk, dv = jax.lax.fori_loop(0, n_needed, body, (dq0, dk0, dv0))
    dq = dq.transpose(0, 3, 1, 2, 4).astype(q.dtype)  # (B,Sq,KV,G,hd)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype), jnp.zeros_like(q_pos)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def sharded_attention(q, k, v, rules, *, causal: bool, block_kv: int = 512, impl: str = "allgather"):
    """Sequence-parallel attention over the "model" axis via shard_map.

    q: (B, S, H, hd); k, v: (B, Skv, KV, hd) — all sequence-sharded on
    "model", batch on the rules' batch axes.
    """
    mesh = rules.mesh
    B, S, H, hd = q.shape
    KV = k.shape[2]
    n_model = mesh.shape["model"]
    scale = 1.0 / math.sqrt(hd)
    bspec = rules.batch_axes if rules.batch_axes else None
    if isinstance(bspec, tuple) and len(bspec) == 1:
        bspec = bspec[0]
    qkv_spec = P(bspec, "model", None, None)

    if impl == "ring":
        if rules.kind == "train":  # rotation loop is fwd-only; train uses flash VJP
            impl = "flash"
        else:
            from repro.runtime.ring_attention import ring_attention_shmap

            return ring_attention_shmap(
                q, k, v, rules, causal=causal, block_kv=block_kv, scale=scale
            )

    differentiable = rules.kind == "train"
    use_flash_vjp = impl == "flash"

    def local(ql, kl, vl):
        i = jax.lax.axis_index("model")
        kg = jax.lax.all_gather(kl, "model", axis=1, tiled=True)  # (B_l, S, KV, hd)
        vg = jax.lax.all_gather(vl, "model", axis=1, tiled=True)
        Sq = ql.shape[1]
        qg = ql.reshape(ql.shape[0], Sq, KV, H // KV, hd)
        if use_flash_vjp:
            q_pos = (i * Sq + jnp.arange(Sq)).astype(jnp.float32)
            out = flash_attention(qg, kg, vg, q_pos, causal, block_kv, scale)
        else:
            out = local_flash(
                qg, kg, vg, q_offset=i * Sq, causal=causal, block_kv=block_kv,
                scale=scale, differentiable=differentiable,
            )
        return out.reshape(ql.shape[0], Sq, H, hd)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )
    return fn(q, k, v).astype(v.dtype)
