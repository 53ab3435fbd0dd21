"""Ring attention: sequence-parallel prefill with overlapped KV rotation.

Instead of one bulk all-gather of K/V per layer (the baseline schedule),
each "model" shard holds its local KV block and the blocks rotate around
the ring via collective-permute — at step j shard i processes the block
originating at shard (i - j) mod n while the next block is in flight. The
total bytes moved match the all-gather, but:

* peak memory holds ONE rotating block instead of the full gathered KV
  ((n-1)/n less transient footprint), and
* every transfer is a neighbour permute that overlaps with the block's
  compute (the roofline max() model assumes overlap; on hardware this is
  what makes it true).

Forward-only (prefill/serve): the rotation loop uses fori_loop and is not
reverse-differentiable; the train path uses the custom-VJP flash instead
(runtime/sharded_attention.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def ring_attention_shmap(q, k, v, rules, *, causal: bool, block_kv: int, scale: float):
    """q: (B,S,H,hd); k, v: (B,S,KV,hd) — all sequence-sharded on "model"."""
    mesh = rules.mesh
    n = mesh.shape["model"]
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    bspec = rules.batch_axes if rules.batch_axes else None
    if isinstance(bspec, tuple) and len(bspec) == 1:
        bspec = bspec[0]
    spec = P(bspec, "model", None, None)
    perm = [(s, (s + 1) % n) for s in range(n)]

    def local(ql, kl, vl):
        i = jax.lax.axis_index("model")
        S_l = ql.shape[1]
        qg = (ql.reshape(ql.shape[0], S_l, KV, G, hd).astype(jnp.float32) * scale)
        q_pos = (i * S_l + jnp.arange(S_l)).astype(jnp.float32)

        acc0 = jnp.zeros((ql.shape[0], KV, G, S_l, hd), jnp.float32)
        m0 = jnp.full((ql.shape[0], KV, G, S_l), NEG_INF, jnp.float32)
        l0 = jnp.zeros((ql.shape[0], KV, G, S_l), jnp.float32)

        def step(j, carry):
            acc, m, l, k_blk, v_blk = carry
            src = (i - j) % n  # shard of origin of the block we now hold
            k_pos = (src * S_l + jnp.arange(S_l)).astype(jnp.float32)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_blk.astype(jnp.float32))
            if causal:
                s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # guard fully-masked blocks (future KV): exp(NEG_INF - NEG_INF)
            m_safe = jnp.maximum(m_new, -1e30)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            alpha = jnp.where(m > NEG_INF / 2, jnp.exp(m - m_safe), 1.0)
            l = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, v_blk.astype(jnp.float32)
            )
            # rotate: send our current block to the next shard
            k_nxt = jax.lax.ppermute(k_blk, "model", perm)
            v_nxt = jax.lax.ppermute(v_blk, "model", perm)
            return acc, m_new, l, k_nxt, v_nxt

        acc, m, l, _, _ = jax.lax.fori_loop(0, n, step, (acc0, m0, l0, kl, vl))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.transpose(0, 3, 1, 2, 4).reshape(ql.shape[0], S_l, H, hd).astype(vl.dtype)

    fn = shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )
    return fn(q, k, v)
