"""Vocab-parallel embedding + cross-entropy (Megatron-style) via shard_map.

Problem: final hidden states are sequence-sharded on "model" while the output
head is vocab-sharded on "model" — full (B,S,V) logits cannot exist, and a
GSPMD seq-chunk scan over a sharded dim serializes. Solution: each shard
all-gathers the (small) hidden states for its batch shard, computes logits
against its local vocab slice in sequence chunks, and the softmax reductions
run as pmax/psum over "model". Collective volume per step: one hidden
all-gather (B_l*S*d) + O(B*S) scalars — independent of vocab size.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def vocab_parallel_embed(tokens: jax.Array, embed: jax.Array, rules) -> jax.Array:
    """Embedding lookup with a vocab-sharded table.

    GSPMD lowers a plain ``embed[tokens]`` by all-gathering the full table
    (measured: 4.4 GiB f32 per step for the 1T config). Instead: each shard
    gathers from its local vocab slice (out-of-range rows -> 0) and a psum
    over "model" assembles the result — collective volume is one activation,
    independent of vocab size. Output is sequence-sharded like the tokens.
    """
    mesh = rules.mesh
    n_model = mesh.shape["model"]
    Vp = embed.shape[0]
    vshard = Vp // n_model
    bspec = rules.batch_axes if rules.batch_axes else None
    if isinstance(bspec, tuple) and len(bspec) == 1:
        bspec = bspec[0]
    seq_axis = "model" if tokens.shape[1] % n_model == 0 and tokens.shape[1] > 1 else None

    def local(tl, el):
        i = jax.lax.axis_index("model")
        if seq_axis is not None:
            # every vocab shard needs the *full* token slice of this batch
            # shard: gather the (cheap, int32) tokens, embed against the
            # local vocab slice, reduce-scatter back to sequence shards
            tl = jax.lax.all_gather(tl, "model", axis=1, tiled=True)  # (B_l, S)
        t_loc = tl - i * vshard
        in_range = (t_loc >= 0) & (t_loc < vshard)
        safe = jnp.clip(t_loc, 0, vshard - 1)
        x = el[safe]  # (B_l, S, d) partial (only local-vocab hits)
        x = jnp.where(in_range[..., None], x, jnp.zeros((), x.dtype))
        if seq_axis is not None:
            return jax.lax.psum_scatter(x, "model", scatter_dimension=1, tiled=True)
        return jax.lax.psum(x, "model")

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(bspec, seq_axis), P("model", None)),
        out_specs=P(bspec, seq_axis, None),
        check_vma=False,
    )
    return fn(tokens, embed)


def vocab_parallel_cross_entropy(
    x: jax.Array,          # (B, S, D) seq-sharded on "model"
    head: jax.Array,       # (Vp, D) vocab-sharded on "model"
    targets: jax.Array,    # (B, S) int32
    mask: jax.Array,       # (B, S) float
    rules,
    *,
    chunk: int = 512,
) -> tuple[jax.Array, jax.Array]:
    """Returns (sum_nll, sum_mask) as replicated scalars."""
    mesh = rules.mesh
    n_model = mesh.shape["model"]
    B, S, D = x.shape
    Vp = head.shape[0]
    vshard = Vp // n_model
    bspec = rules.batch_axes if rules.batch_axes else None
    if isinstance(bspec, tuple) and len(bspec) == 1:
        bspec = bspec[0]

    cs = min(chunk, S)
    while S % cs:
        cs -= 1
    n_chunks = S // cs

    def local(xl, hl, tl, ml):
        i = jax.lax.axis_index("model")
        xg = jax.lax.all_gather(xl, "model", axis=1, tiled=True)  # (B_l, S, D)
        tg = jax.lax.all_gather(tl, "model", axis=1, tiled=True)  # (B_l, S)
        mg = jax.lax.all_gather(ml, "model", axis=1, tiled=True)
        B_l = xg.shape[0]
        xc = xg.reshape(B_l, n_chunks, cs, D).swapaxes(0, 1)
        tc = tg.reshape(B_l, n_chunks, cs).swapaxes(0, 1)
        mc = mg.reshape(B_l, n_chunks, cs).swapaxes(0, 1)
        hT = hl.astype(xl.dtype).T  # (D, vshard)

        def step(xi, ti, mi):
            logits = (xi @ hT).astype(jnp.float32)  # (B_l, cs, vshard)
            # stabilization constant only -> gradients cancel exactly
            lmax = jax.lax.stop_gradient(
                jax.lax.pmax(jax.lax.stop_gradient(logits).max(axis=-1), "model")
            )
            sumexp = jax.lax.psum(jnp.exp(logits - lmax[..., None]).sum(axis=-1), "model")
            lse = jnp.log(sumexp) + lmax
            t_loc = ti - i * vshard
            in_range = (t_loc >= 0) & (t_loc < vshard)
            safe = jnp.clip(t_loc, 0, vshard - 1)
            picked_loc = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
            picked = jax.lax.psum(jnp.where(in_range, picked_loc, 0.0), "model")
            return ((lse - picked) * mi).sum()

        tot, _ = jax.lax.scan(
            lambda acc, c: (acc + step(*c), None), jnp.float32(0.0), (xc, tc, mc))
        # reduce over batch shards -> replicated scalar
        axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
        if axes:
            tot = jax.lax.psum(tot, axes)
        return tot

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(bspec, "model", None), P("model", None), P(bspec, "model"), P(bspec, "model")),
        out_specs=P(),
        check_vma=False,
    )
    # the mask count needs no sharded compute, so it stays outside the map
    return fn(x, head, targets, mask), mask.astype(jnp.float32).sum()
