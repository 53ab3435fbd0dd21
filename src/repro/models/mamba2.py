"""Mamba2 block (SSD — state-space duality, chunked matmul form).

Recurrence per head (state S in R^{headdim x d_state}):
    S_t = exp(dt_t * A) S_{t-1} + (dt_t x_t) B_t^T
    y_t = S_t C_t + D x_t
``ssd_chunked`` is the matmul-heavy chunked algorithm of the Mamba2 paper
(intra-chunk (C,C) scalar decay masks -> MXU-friendly); ``ssd_recurrent`` is
the token-level oracle used for decode and tests.

The depthwise causal conv (width 4) is implemented as explicit shifts + MACs
(elementwise; avoids conv ops so the HLO cost model stays dot-only).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ParamSpec, rms_norm


def conv1d_causal(x: jax.Array, w: jax.Array, b: jax.Array, state: jax.Array | None):
    """Depthwise causal conv. ``x``: (B,T,Ch); ``w``: (K,Ch); ``state``: (B,K-1,Ch)."""
    K = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)  # (B, T+K-1, Ch)
    T = x.shape[1]
    out = sum(xp[:, i : i + T] * w[i][None, None] for i in range(K)) + b[None, None]
    new_state = xp[:, -(K - 1) :]
    return jax.nn.silu(out), new_state


def ssd_recurrent(x, dt, A, B, C, D, state):
    """Oracle/decode SSD.

    x: (Bt,T,H,P); dt: (Bt,T,H); A: (H,) negative; B,C: (Bt,T,G,N) with G=1;
    D: (H,); state: (Bt,H,P,N). Returns (y, state).
    """

    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp  # (Bt,H,P), (Bt,H), (Bt,G,N), (Bt,G,N)
        decay = jnp.exp(dt_t * A[None])  # (Bt,H)
        dBx = jnp.einsum("bhp,bn->bhpn", x_t * dt_t[..., None], B_t[:, 0])
        S = decay[..., None, None] * S + dBx
        y = jnp.einsum("bhpn,bn->bhp", S, C_t[:, 0]) + D[None, :, None] * x_t
        return S, y

    xs = x.swapaxes(0, 1)
    dts = dt.swapaxes(0, 1)
    Bs = B.swapaxes(0, 1)
    Cs = C.swapaxes(0, 1)
    state, ys = jax.lax.scan(step, state, (xs, dts, Bs, Cs))
    return ys.swapaxes(0, 1), state


def ssd_chunked(x, dt, A, B, C, D, state, *, chunk: int = 64, checkpoint_chunks: bool = False):
    """Chunked SSD (Mamba2 paper alg.); same semantics as ``ssd_recurrent``.
    ``checkpoint_chunks`` remats chunk bodies (backward recomputes the (C,C)
    decay masks instead of saving them)."""
    Bt, T, H, P = x.shape
    N = B.shape[-1]
    Cn = min(chunk, T)
    assert T % Cn == 0, (T, Cn)
    n = T // Cn

    xc = x.reshape(Bt, n, Cn, H, P).transpose(1, 0, 3, 2, 4)  # (n,Bt,H,C,P)
    dtc = dt.reshape(Bt, n, Cn, H).transpose(1, 0, 3, 2)  # (n,Bt,H,C)
    Bc = B[:, :, 0].reshape(Bt, n, Cn, N).transpose(1, 0, 2, 3)  # (n,Bt,C,N)
    Cc = C[:, :, 0].reshape(Bt, n, Cn, N).transpose(1, 0, 2, 3)

    tri = jnp.tril(jnp.ones((Cn, Cn), bool))  # a <= t

    def chunk_step(S, inp):
        x_i, dt_i, B_i, C_i = inp
        dA = dt_i * A[None, :, None]  # (Bt,H,C), <= 0
        cum = jnp.cumsum(dA, axis=-1)  # inclusive
        # intra: scores[t,a] = exp(cum_t - cum_a) * (C_t . B_a) * dt_a,  a <= t
        L = jnp.exp(jnp.clip(cum[..., :, None] - cum[..., None, :], -60.0, 0.0))
        L = jnp.where(tri[None, None], L, 0.0)
        CB = jnp.einsum("btn,ban->bta", C_i, B_i)  # (Bt,C,C)
        scores = CB[:, None] * L * dt_i[..., None, :]  # (Bt,H,C,C)
        y = jnp.einsum("bhta,bhap->bhtp", scores, x_i)
        # inter: y += (C_t exp(cum_t)) . S
        y = y + jnp.einsum("btn,bht,bhpn->bhtp", C_i, jnp.exp(cum), S)
        # state update
        last = cum[..., -1:]  # (Bt,H,1)
        w = jnp.exp(jnp.clip(last - cum, -60.0, 0.0)) * dt_i  # (Bt,H,C)
        dBx = jnp.einsum("bhtp,bht,btn->bhpn", x_i, w, B_i)
        S = jnp.exp(last[..., 0])[..., None, None] * S + dBx
        return S, y

    step = jax.checkpoint(chunk_step, prevent_cse=False) if checkpoint_chunks else chunk_step
    state, ys = jax.lax.scan(step, state, (xc, dtc, Bc, Cc))
    ys = ys.transpose(1, 0, 3, 2, 4).reshape(Bt, T, H, P)
    return ys + D[None, None, :, None] * x, state


# ---------------------------------------------------------------------------
# block
# ---------------------------------------------------------------------------


def mamba_specs(cfg, n_layers: int) -> dict:
    d, di, ds = cfg.d_model, cfg.d_inner, cfg.ssm_state
    H = cfg.n_ssm_heads
    conv_ch = di + 2 * ds  # x, B, C  (ngroups=1)
    dt = jnp.dtype(cfg.param_dtype)
    L = n_layers
    return {
        "norm": ParamSpec((L, d), ("layers", "embed"), jnp.float32, init="ones"),
        "w_in": ParamSpec((L, d, 2 * di + 2 * ds + H), ("layers", "embed", "mlp"), dt),
        "conv_w": ParamSpec((L, cfg.conv_width, conv_ch), ("layers", None, "mlp"), jnp.float32),
        "conv_b": ParamSpec((L, conv_ch), ("layers", "mlp"), jnp.float32, init="zeros"),
        "A_log": ParamSpec((L, H), ("layers", None), jnp.float32, init="small"),
        "D": ParamSpec((L, H), ("layers", None), jnp.float32, init="ones"),
        "dt_bias": ParamSpec((L, H), ("layers", None), jnp.float32, init="small"),
        "ssd_norm": ParamSpec((L, di), ("layers", "mlp"), jnp.float32, init="ones"),
        "w_out": ParamSpec((L, di, d), ("layers", "mlp", "embed"), dt),
    }


def mamba_state_struct(cfg, n_layers: int, batch: int) -> dict:
    di, ds = cfg.d_inner, cfg.ssm_state
    H, P = cfg.n_ssm_heads, cfg.ssm_head_dim
    conv_ch = di + 2 * ds
    return {
        "conv": jax.ShapeDtypeStruct((n_layers, batch, cfg.conv_width - 1, conv_ch), jnp.dtype(cfg.compute_dtype)),
        "ssd": jax.ShapeDtypeStruct((n_layers, batch, H, P, ds), jnp.float32),
    }


def mamba_state_axes() -> dict:
    return {
        "conv": ("layers", "batch", None, "mlp"),
        "ssd": ("layers", "batch", None, None, None),
    }


def mamba_apply(cfg, lp: dict, x: jax.Array, state: dict | None, *, compute_dtype, chunked: bool):
    """One Mamba2 block. ``x``: (B,T,d). Returns (out, new_state)."""
    cd = compute_dtype
    di, ds = cfg.d_inner, cfg.ssm_state
    H, P = cfg.n_ssm_heads, cfg.ssm_head_dim
    B_, T, _ = x.shape

    h = rms_norm(x, lp["norm"], cfg.norm_eps)
    zxbcdt = h.astype(cd) @ lp["w_in"].astype(cd)
    z, xs, Bm, Cm, dt = jnp.split(zxbcdt, [di, 2 * di, 2 * di + ds, 2 * di + 2 * ds], axis=-1)
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
    conv_state = None if state is None else state["conv"]

    from repro.runtime.sharding import _CTX

    rules = getattr(_CTX, "rules", None)
    if (
        state is None
        and rules is not None
        and rules.mesh.shape.get("model", 1) > 1
        and T % rules.mesh.shape["model"] == 0
        and T > 1
    ):
        from repro.runtime.sequence_parallel import conv1d_sharded

        conv_out = conv1d_sharded(conv_in, lp["conv_w"].astype(cd), lp["conv_b"].astype(cd), rules)
        new_conv = conv_in[:, -(cfg.conv_width - 1) :]
    else:
        conv_out, new_conv = conv1d_causal(conv_in, lp["conv_w"].astype(cd), lp["conv_b"].astype(cd), conv_state)
    xs, Bm, Cm = jnp.split(conv_out, [di, di + ds], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"][None, None])  # (B,T,H)
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))  # (H,)
    xh = xs.reshape(B_, T, H, P).astype(jnp.float32)
    Bg = Bm[:, :, None, :].astype(jnp.float32)  # (B,T,1,N)
    Cg = Cm[:, :, None, :].astype(jnp.float32)
    S0 = jnp.zeros((B_, H, P, ds), jnp.float32) if state is None else state["ssd"]
    # sequence-parallel core when activations are seq-sharded
    from repro.runtime.sharding import _CTX

    rules = getattr(_CTX, "rules", None)
    if (
        chunked
        and state is None
        and rules is not None
        and rules.mesh.shape.get("model", 1) > 1
        and T % rules.mesh.shape["model"] == 0
        and T > 1
    ):
        from repro.runtime.sequence_parallel import ssd_sharded

        y, new_ssd = ssd_sharded(xh, dt, A, Bg, Cg, lp["D"].astype(jnp.float32), rules)
    else:
        fn = ssd_chunked if chunked else ssd_recurrent
        y, new_ssd = fn(xh, dt, A, Bg, Cg, lp["D"].astype(jnp.float32), S0)
    y = y.reshape(B_, T, di)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(y.astype(cd), lp["ssd_norm"], cfg.norm_eps)
    out = y @ lp["w_out"].astype(cd)
    new_state = {"conv": new_conv.astype(cd), "ssd": new_ssd}
    return out, new_state
