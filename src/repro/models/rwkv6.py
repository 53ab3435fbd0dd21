"""RWKV6 ("Finch") — attention-free LM with data-dependent per-channel decay.

Two numerically-equivalent WKV6 implementations:

* ``wkv6_recurrent`` — token-by-token ``lax.scan`` (decode path + test oracle)
* ``wkv6_chunked``  — chunked-parallel form used for train/prefill. All decay
  exponents are differences of within-chunk cumulative log-decays and hence
  <= 0 (no overflow); the intra-chunk score needs a per-channel decay factor
  so it is a 3-operand einsum (VPU work; the channel-mix matmuls dominate
  FLOPs by ~300x).

State per layer/head: S in R^{N x N} (key-dim x value-dim):
    o_t = r_t^T (S_{t-1} + u . k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models.base import BaseModel
from repro.models.common import (
    embed_lookup,
    ParamSpec,
    chunked_cross_entropy,
    group_norm,
    rms_norm,
    shift_targets,
)

MIX_LORA = 32  # ddlerp lora rank (5 heads)
DECAY_LORA = 64


# ---------------------------------------------------------------------------
# WKV6 core
# ---------------------------------------------------------------------------


def wkv6_recurrent(r, k, v, w, u, state):
    """Oracle/decode WKV. r,k,v,w: (B,H,T,N); u: (H,N); state: (B,H,N,N)."""

    def step(S, inp):
        r_t, k_t, v_t, w_t = inp  # (B,H,N)
        kv = k_t[..., :, None] * v_t[..., None, :]  # (B,H,N,N)
        o = jnp.einsum("bhi,bhij->bhj", r_t, S + u[None, :, :, None] * kv)
        S = w_t[..., :, None] * S + kv
        return S, o

    rs, ks, vs, ws = (x.swapaxes(0, 2).swapaxes(1, 2) for x in (r, k, v, w))  # (T,B,H,N)
    state, outs = jax.lax.scan(step, state, (rs, ks, vs, ws))
    return outs.transpose(1, 2, 0, 3), state  # (B,H,T,N)


def wkv6_chunked(r, k, v, w, u, state, *, chunk: int = 32, checkpoint_chunks: bool = False):
    """Chunked-parallel WKV. Same signature/semantics as ``wkv6_recurrent``.
    ``checkpoint_chunks`` remats each chunk step so backward recomputes the
    (C,C,N) decay tensors instead of saving them (train path)."""
    B, H, T, N = r.shape
    C = min(chunk, T)
    assert T % C == 0, (T, C)
    n = T // C

    def to_chunks(x):
        return x.reshape(B, H, n, C, N).transpose(2, 0, 1, 3, 4)  # (n,B,H,C,N)

    rc, kc, vc = to_chunks(r), to_chunks(k), to_chunks(v)
    lw = jnp.log(jnp.maximum(to_chunks(w), 1e-38))  # (n,B,H,C,N), <= 0
    clog = jnp.cumsum(lw, axis=-2)  # inclusive cumulative log decay
    cprev = clog - lw  # exclusive

    tri = jnp.tril(jnp.ones((C, C), bool), k=-1)  # strictly lower: a < t

    def chunk_step(S, inp):
        r_i, k_i, v_i, clog_i, cprev_i = inp
        # intra-chunk: scores[t,a] = sum_i r[t,i] k[a,i] exp(cprev[t,i]-clog[a,i])
        decay = jnp.exp(
            jnp.clip(cprev_i[..., :, None, :] - clog_i[..., None, :, :], -60.0, 0.0)
        )  # (B,H,C,C,N)
        decay = jnp.where(tri[None, None, :, :, None], decay, 0.0)
        # diagonal bonus term u
        scores = jnp.einsum("bhti,bhai,bhtai->bhta", r_i, k_i, decay)
        diag = jnp.einsum("bhti,hi->bht", r_i * k_i, u)
        o = jnp.einsum("bhta,bhaj->bhtj", scores, v_i) + diag[..., None] * v_i
        # inter-chunk: carry-in state
        o = o + jnp.einsum("bhti,bhij->bhtj", r_i * jnp.exp(cprev_i), S)
        # state update
        last = clog_i[..., -1:, :]  # (B,H,1,N)
        k_hat = k_i * jnp.exp(last - clog_i)
        S = jnp.exp(last[..., 0, :])[..., :, None] * S + jnp.einsum(
            "bhai,bhaj->bhij", k_hat, v_i
        )
        return S, o

    step = jax.checkpoint(chunk_step, prevent_cse=False) if checkpoint_chunks else chunk_step
    state, outs = jax.lax.scan(step, state, (rc, kc, vc, clog, cprev))
    outs = outs.transpose(1, 2, 0, 3, 4).reshape(B, H, T, N)
    return outs, state


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------


class Rwkv6LM(BaseModel):
    def param_specs(self) -> dict:
        cfg = self.cfg
        d, L = cfg.d_model, cfg.n_layers
        H, N = cfg.n_rwkv_heads, cfg.rwkv_head_dim
        dt = self.param_dtype
        layers = {
            "ln1": ParamSpec((L, d), ("layers", "embed"), jnp.float32, init="ones"),
            "ln2": ParamSpec((L, d), ("layers", "embed"), jnp.float32, init="ones"),
            # time-mix ddlerp
            "tm_mix_x": ParamSpec((L, d), ("layers", "embed"), jnp.float32, init="small"),
            "tm_mix": ParamSpec((L, 5, d), ("layers", None, "embed"), jnp.float32, init="small"),
            "tm_lora_a": ParamSpec((L, d, 5 * MIX_LORA), ("layers", "embed", None), dt),
            "tm_lora_b": ParamSpec((L, 5, MIX_LORA, d), ("layers", None, None, "embed"), dt, init="small"),
            # projections (fused dims shard on "heads")
            "w_r": ParamSpec((L, d, H * N), ("layers", "embed", "heads"), dt),
            "w_k": ParamSpec((L, d, H * N), ("layers", "embed", "heads"), dt),
            "w_v": ParamSpec((L, d, H * N), ("layers", "embed", "heads"), dt),
            "w_g": ParamSpec((L, d, H * N), ("layers", "embed", "heads"), dt),
            "w_o": ParamSpec((L, H * N, d), ("layers", "heads", "embed"), dt),
            # data-dependent decay
            "decay_base": ParamSpec((L, H * N), ("layers", "heads"), jnp.float32, init="small"),
            "decay_lora_a": ParamSpec((L, d, DECAY_LORA), ("layers", "embed", None), dt),
            "decay_lora_b": ParamSpec((L, DECAY_LORA, H * N), ("layers", None, "heads"), dt, init="small"),
            "u_bonus": ParamSpec((L, H, N), ("layers", None, None), jnp.float32, init="small"),
            "wkv_norm_scale": ParamSpec((L, H * N), ("layers", "heads"), jnp.float32, init="ones"),
            "wkv_norm_bias": ParamSpec((L, H * N), ("layers", "heads"), jnp.float32, init="zeros"),
            # channel-mix
            "cm_mix_k": ParamSpec((L, d), ("layers", "embed"), jnp.float32, init="small"),
            "cm_mix_r": ParamSpec((L, d), ("layers", "embed"), jnp.float32, init="small"),
            "cm_k": ParamSpec((L, d, cfg.d_ff), ("layers", "embed", "mlp"), dt),
            "cm_v": ParamSpec((L, cfg.d_ff, d), ("layers", "mlp", "embed"), dt),
            "cm_r": ParamSpec((L, d, d), ("layers", "embed", None), dt),
        }
        return {
            "embed": ParamSpec((cfg.padded_vocab, d), ("vocab", "embed"), dt, init="normal"),
            "final_norm": ParamSpec((d,), ("embed",), jnp.float32, init="ones"),
            "lm_head": ParamSpec((d, cfg.padded_vocab), ("embed", "vocab"), dt),
            "layers": layers,
        }

    # ---- layer pieces ------------------------------------------------------

    def _time_mix(self, lp, x, shift_state, wkv_state, *, chunked: bool):
        cfg = self.cfg
        cd = self.compute_dtype
        H, N = cfg.n_rwkv_heads, cfg.rwkv_head_dim
        B, T, d = x.shape
        prev = jnp.concatenate([shift_state[:, None], x[:, :-1]], axis=1)
        xx = prev - x
        base = x + xx * lp["tm_mix_x"].astype(x.dtype)
        s = jnp.tanh(base.astype(cd) @ lp["tm_lora_a"].astype(cd))
        s = s.reshape(B, T, 5, MIX_LORA)
        delta = jnp.einsum("btfr,frd->btfd", s, lp["tm_lora_b"].astype(cd))  # (B,T,5,d)
        mix = lp["tm_mix"].astype(cd)[None, None] + delta  # (B,T,5,d)
        xw, xk, xv, xr, xg = [
            (x + xx * mix[:, :, i]).astype(cd) for i in range(5)
        ]
        r = (xr @ lp["w_r"].astype(cd)).reshape(B, T, H, N)
        k = (xk @ lp["w_k"].astype(cd)).reshape(B, T, H, N)
        v = (xv @ lp["w_v"].astype(cd)).reshape(B, T, H, N)
        g = jax.nn.silu(xg @ lp["w_g"].astype(cd))
        dlogit = lp["decay_base"].astype(jnp.float32) + (
            jnp.tanh(xw @ lp["decay_lora_a"].astype(cd)) @ lp["decay_lora_b"].astype(cd)
        ).astype(jnp.float32)
        w = jnp.exp(-jnp.exp(dlogit.reshape(B, T, H, N)))  # (0,1) per channel

        to_bhtn = lambda a: a.transpose(0, 2, 1, 3).astype(jnp.float32)
        u = lp["u_bonus"].astype(jnp.float32)
        # sequence-parallel core when the activations are seq-sharded (a
        # chunk scan over a sharded dim would serialize across shards)
        from repro.runtime.sharding import _CTX

        rules = getattr(_CTX, "rules", None)
        if (
            chunked
            and rules is not None
            and rules.mesh.shape.get("model", 1) > 1
            and T % rules.mesh.shape["model"] == 0
            and T > 1
        ):
            from repro.runtime.sequence_parallel import wkv6_sharded

            o, wkv_state = wkv6_sharded(
                to_bhtn(r), to_bhtn(k), to_bhtn(v), to_bhtn(w), u, rules
            )
        else:
            fn = wkv6_chunked if chunked else wkv6_recurrent
            o, wkv_state = fn(to_bhtn(r), to_bhtn(k), to_bhtn(v), to_bhtn(w), u, wkv_state)
        o = o.transpose(0, 2, 1, 3).reshape(B, T, H * N)
        o = group_norm(o, H, lp["wkv_norm_scale"], lp["wkv_norm_bias"], 64e-5)
        out = (o.astype(cd) * g) @ lp["w_o"].astype(cd)
        return out, x[:, -1], wkv_state

    def _channel_mix(self, lp, x, shift_state):
        cd = self.compute_dtype
        prev = jnp.concatenate([shift_state[:, None], x[:, :-1]], axis=1)
        xx = prev - x
        xk = (x + xx * lp["cm_mix_k"].astype(x.dtype)).astype(cd)
        xr = (x + xx * lp["cm_mix_r"].astype(x.dtype)).astype(cd)
        kk = jnp.square(jax.nn.relu(xk @ lp["cm_k"].astype(cd)))
        out = jax.nn.sigmoid(xr @ lp["cm_r"].astype(cd)) * (kk @ lp["cm_v"].astype(cd))
        return out, x[:, -1]

    # ---- forward -----------------------------------------------------------

    def _layer_fn(self, chunked: bool, collect_state: bool):
        cfg = self.cfg

        def layer(x, lp, states=None):
            B = x.shape[0]
            H, N = cfg.n_rwkv_heads, cfg.rwkv_head_dim
            if states is None:
                tm_shift = jnp.zeros((B, cfg.d_model), x.dtype)
                cm_shift = jnp.zeros((B, cfg.d_model), x.dtype)
                wkv = jnp.zeros((B, H, N, N), jnp.float32)
            else:
                tm_shift, cm_shift, wkv = states["tm_shift"], states["cm_shift"], states["wkv"]
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            a, tm_shift, wkv = self._time_mix(lp, h, tm_shift, wkv, chunked=chunked)
            x = x + a
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            m, cm_shift = self._channel_mix(lp, h, cm_shift)
            x = x + m
            new_states = {"tm_shift": tm_shift, "cm_shift": cm_shift, "wkv": wkv}
            return x, new_states

        return layer

    def _forward(self, params, tokens, *, collect_state: bool):
        cfg = self.cfg
        x = embed_lookup(params["embed"], tokens).astype(self.compute_dtype)
        layer = self._layer_fn(chunked=True, collect_state=collect_state)

        def body(x, lp):
            x, states = layer(x, lp)
            return x, states if collect_state else None

        if cfg.remat != "none":
            policy = None if cfg.remat == "full" else jax.checkpoint_policies.checkpoint_dots
            body = jax.checkpoint(body, policy=policy, prevent_cse=False)
        x, states = jax.lax.scan(body, x, params["layers"])
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, states

    # ---- public API ----------------------------------------------------------

    def loss(self, params, batch):
        tokens = batch["tokens"]
        x, _ = self._forward(params, tokens, collect_state=False)
        targets, mask = shift_targets(tokens, batch.get("mask"))
        tot, cnt = chunked_cross_entropy(x, params["lm_head"].T, targets, mask, vocab_size=self.cfg.vocab_size)
        loss = tot / jnp.maximum(cnt, 1.0)
        return loss, {"ce_loss": loss, "tokens": cnt}

    def prefill(self, params, batch):
        x, states = self._forward(params, batch["tokens"], collect_state=True)
        logits = x[:, -1:].astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
        return logits, states

    def decode(self, params, cache, batch):
        cfg = self.cfg
        x = embed_lookup(params["embed"], batch["tokens"]).astype(self.compute_dtype)  # (B,1,d)
        layer = self._layer_fn(chunked=False, collect_state=True)

        def body(x, inp):
            lp, states = inp
            x, new_states = layer(x, lp, states)
            return x, new_states

        x, states = jax.lax.scan(body, x, (params["layers"], cache))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
        return logits, states

    # ---- input structs --------------------------------------------------------

    def input_specs(self, shape: ShapeConfig) -> dict:
        B, S = shape.global_batch, shape.seq_len
        if shape.kind == "decode":
            return {
                "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                "positions": jax.ShapeDtypeStruct((B,), jnp.int32),
            }
        return {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}

    def input_axes(self, shape: ShapeConfig) -> dict:
        if shape.kind == "decode":
            return {"tokens": ("batch", None), "positions": ("batch",)}
        return {"tokens": ("batch", "seq")}

    def cache_struct(self, shape: ShapeConfig):
        cfg = self.cfg
        B, L = shape.global_batch, cfg.n_layers
        H, N = cfg.n_rwkv_heads, cfg.rwkv_head_dim
        return {
            "tm_shift": jax.ShapeDtypeStruct((L, B, cfg.d_model), jnp.dtype(cfg.compute_dtype)),
            "cm_shift": jax.ShapeDtypeStruct((L, B, cfg.d_model), jnp.dtype(cfg.compute_dtype)),
            "wkv": jax.ShapeDtypeStruct((L, B, H, N, N), jnp.float32),
        }

    def cache_axes(self, shape: ShapeConfig):
        return {
            "tm_shift": ("layers", "batch", "embed"),
            "cm_shift": ("layers", "batch", "embed"),
            "wkv": ("layers", "batch", None, None, None),
        }
