"""Sequence-parallel linear-recurrence cores (WKV6 / Mamba2-SSD).

Problem: a chunked scan over a sequence-sharded chunk dim serializes
across shards under GSPMD (each step lives on one shard) and AD
materializes per-chunk decay tensors — rwkv6 training at 4k tokens
showed 4.8e14 B/device traffic and a 113 GiB peak.

Fix — the distributed linear-attention decomposition. Linear recurrences
compose associatively:

    S_shard_i = D_i * S_start_i + S_i^local,   D_i = prod of decays in shard i

so each "model" shard (1) runs its local chunked core with S0 = 0, (2)
all-gathers the tiny per-shard (S_i^local, D_i) summaries, (3) computes its
exclusive prefix S_start_i locally, and (4) adds the closed-form correction
``out_t += (r_t * decay_from_shard_start(t)) @ S_start_i``. One collective of
O(H*N*N) bytes per layer replaces the serialized global scan. Chunk bodies
are jax.checkpoint-ed so backward recomputes the decay tensors instead of
saving them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _bspec(rules):
    b = rules.batch_axes if rules.batch_axes else None
    if isinstance(b, tuple) and len(b) == 1:
        b = b[0]
    return b


# ---------------------------------------------------------------------------
# WKV6
# ---------------------------------------------------------------------------


def wkv6_sharded(r, k, v, w, u, rules, *, chunk: int = 32):
    """Sequence-parallel WKV6. r,k,v,w: (B,H,T,N) with T sharded on "model";
    initial state is zeros (train/prefill from scratch). Returns (out, state)
    with state replicated."""
    from repro.models.rwkv6 import wkv6_chunked

    mesh = rules.mesh
    n_model = mesh.shape["model"]
    bspec = _bspec(rules)
    spec = P(bspec, None, "model", None)

    def local(r_l, k_l, v_l, w_l, u_l):
        B, H, T_l, N = r_l.shape
        i = jax.lax.axis_index("model")
        S0 = jnp.zeros((B, H, N, N), jnp.float32)
        out_local, S_local = wkv6_chunked(
            r_l, k_l, v_l, w_l, u_l, S0, chunk=chunk, checkpoint_chunks=True
        )
        # per-shard total decay and within-shard exclusive cumulative decay
        lw = jnp.log(jnp.maximum(w_l, 1e-38))  # (B,H,T,N)
        clog = jnp.cumsum(lw, axis=2)
        D_local = jnp.exp(clog[:, :, -1])  # (B,H,N)
        cprev = jnp.exp(clog - lw)  # decay from shard start, exclusive

        # gather the tiny summaries and fold the exclusive prefix
        S_all = jax.lax.all_gather(S_local, "model")  # (n, B,H,N,N)
        D_all = jax.lax.all_gather(D_local, "model")  # (n, B,H,N)
        S_start = jnp.zeros_like(S_local)
        for j in range(n_model):
            take = j < i
            S_start = jnp.where(take, S_start * D_all[j][..., :, None] + S_all[j], S_start)
        # correction: contributions of earlier shards to local outputs
        out = out_local + jnp.einsum("bhtn,bhnm->bhtm", r_l * cprev, S_start)
        # final global state (identical on every shard after folding all)
        S_final = S_start * D_all[i][..., :, None] + S_local
        last = jnp.where(i == n_model - 1, 1.0, 0.0)
        S_final = jax.lax.psum(S_final * last, "model")
        return out, S_final

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, P(None, None)),
        out_specs=(spec, P(bspec, None, None, None)),
        check_vma=False,
    )
    return fn(r, k, v, w, u)


# ---------------------------------------------------------------------------
# causal depthwise conv with halo exchange
# ---------------------------------------------------------------------------


def conv1d_sharded(x, w, b, rules):
    """Depthwise causal conv over a sequence-sharded ``x`` (B,T,Ch).

    Under GSPMD, the K shifted copies of a sharded dim each force a reshard;
    instead each shard ppermutes its last K-1 rows to its right neighbour
    (the halo) and convolves locally — one tiny collective-permute per layer.
    """
    import jax.nn

    mesh = rules.mesh
    n_model = mesh.shape["model"]
    K = w.shape[0]
    bspec = _bspec(rules)
    spec = P(bspec, "model", None)

    def local(xl, wl, bl):
        i = jax.lax.axis_index("model")
        halo = jax.lax.ppermute(
            xl[:, -(K - 1) :], "model", [(s, (s + 1) % n_model) for s in range(n_model)]
        )
        halo = jnp.where(i == 0, jnp.zeros_like(halo), halo)  # causal start
        xp = jnp.concatenate([halo, xl], axis=1)
        T_l = xl.shape[1]
        out = sum(xp[:, j : j + T_l] * wl[j][None, None] for j in range(K)) + bl[None, None]
        return jax.nn.silu(out)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, P(None, None), P(None)),
        out_specs=spec,
        check_vma=False,
    )
    return fn(x, w, b)


# ---------------------------------------------------------------------------
# Mamba2 SSD
# ---------------------------------------------------------------------------


def ssd_sharded(x, dt, A, B, C, D, rules, *, chunk: int = 64):
    """Sequence-parallel SSD. x: (Bt,T,H,P), dt: (Bt,T,H), B,C: (Bt,T,G,N);
    T sharded on "model"; zero initial state."""
    from repro.models.mamba2 import ssd_chunked

    mesh = rules.mesh
    n_model = mesh.shape["model"]
    bspec = _bspec(rules)
    x_spec = P(bspec, "model", None, None)
    dt_spec = P(bspec, "model", None)
    bc_spec = P(bspec, "model", None, None)

    def local(x_l, dt_l, B_l, C_l):
        Bt, T_l, H, Pd = x_l.shape
        N = B_l.shape[-1]
        i = jax.lax.axis_index("model")
        S0 = jnp.zeros((Bt, H, Pd, N), jnp.float32)
        y_local, S_local = ssd_chunked(
            x_l, dt_l, A, B_l, C_l, D, S0, chunk=chunk, checkpoint_chunks=True
        )
        dA = dt_l * A[None, None]  # (Bt,T,H), <= 0
        cum = jnp.cumsum(dA, axis=1)
        D_local = jnp.exp(cum[:, -1])  # (Bt,H) per-shard decay
        cincl = jnp.exp(cum)  # y_t reads S_t (inclusive decay from shard start)

        S_all = jax.lax.all_gather(S_local, "model")  # (n,Bt,H,P,N)
        D_all = jax.lax.all_gather(D_local, "model")  # (n,Bt,H)
        S_start = jnp.zeros_like(S_local)
        for j in range(n_model):
            take = j < i
            S_start = jnp.where(take, S_start * D_all[j][..., None, None] + S_all[j], S_start)
        # correction: y_t += (C_t * decay_from_start) . S_start
        y = y_local + jnp.einsum(
            "btn,bth,bhpn->bthp", C_l[:, :, 0], cincl, S_start
        )
        S_final = S_start * D_all[i][..., None, None] + S_local
        last = jnp.where(i == n_model - 1, 1.0, 0.0)
        S_final = jax.lax.psum(S_final * last, "model")
        return y, S_final

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(x_spec, dt_spec, bc_spec, bc_spec),
        out_specs=(x_spec, P(bspec, None, None, None)),
        check_vma=False,
    )
    return fn(x, dt, B, C)
