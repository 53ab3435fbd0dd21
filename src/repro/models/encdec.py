"""Encoder-decoder transformer (seamless-m4t backbone).

The speech frontend is a stub: the encoder consumes precomputed frame
embeddings (B, S_enc, d) from ``input_specs()``. The decoder is a standard
causal transformer with cross-attention into the encoder memory; decode
carries a self-attention KV cache plus a static cross-attention cache
computed once at prefill.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import attention as attn_lib
from repro.models.base import BaseModel
from repro.models.common import embed_lookup, ParamSpec, chunked_cross_entropy, rms_norm, shift_targets
from repro.models.ffn import mlp_apply, mlp_specs
from repro.models.transformer import attn_block_apply, attn_block_decode, attn_block_specs


def _cross_attn_specs(cfg: ArchConfig, L: int) -> dict:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "xattn_norm": ParamSpec((L, d), ("layers", "embed"), jnp.float32, init="ones"),
        "wq_x": ParamSpec((L, d, H * hd), ("layers", "embed", "heads"), dt),
        "wkv_x": ParamSpec((L, d, 2 * KV * hd), ("layers", "embed", "kv"), dt),
        "wo_x": ParamSpec((L, H * hd, d), ("layers", "heads", "embed"), dt),
    }


class EncDecLM(BaseModel):
    def param_specs(self) -> dict:
        cfg = self.cfg
        d, dt = cfg.d_model, self.param_dtype
        Le, Ld = cfg.n_enc_layers, cfg.n_layers
        enc_layers = {
            "attn_norm": ParamSpec((Le, d), ("layers", "embed"), jnp.float32, init="ones"),
            "mlp_norm": ParamSpec((Le, d), ("layers", "embed"), jnp.float32, init="ones"),
            **attn_block_specs(cfg, Le),
            **mlp_specs(d, cfg.d_ff, Le, dt),
        }
        dec_layers = {
            "attn_norm": ParamSpec((Ld, d), ("layers", "embed"), jnp.float32, init="ones"),
            "mlp_norm": ParamSpec((Ld, d), ("layers", "embed"), jnp.float32, init="ones"),
            **attn_block_specs(cfg, Ld),
            **_cross_attn_specs(cfg, Ld),
            **mlp_specs(d, cfg.d_ff, Ld, dt),
        }
        return {
            "embed": ParamSpec((cfg.padded_vocab, d), ("vocab", "embed"), dt, init="normal"),
            "frame_proj": ParamSpec((d, d), ("embed", None), dt),
            "enc_final_norm": ParamSpec((d,), ("embed",), jnp.float32, init="ones"),
            "final_norm": ParamSpec((d,), ("embed",), jnp.float32, init="ones"),
            "lm_head": ParamSpec((d, cfg.padded_vocab), ("embed", "vocab"), dt),
            "encoder": enc_layers,
            "decoder": dec_layers,
        }

    # ---- encoder -----------------------------------------------------------

    def _encode(self, params, frame_embeds):
        cfg = self.cfg
        cd = self.compute_dtype
        x = frame_embeds.astype(cd) @ params["frame_proj"].astype(cd)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

        def layer(x, lp):
            h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            a, _ = attn_block_apply(cfg, lp, h, positions=positions, compute_dtype=cd, causal=False)
            x = x + a
            h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            return x + mlp_apply(lp, h, cd), None

        if cfg.remat != "none":
            layer = jax.checkpoint(layer, prevent_cse=False)
        x, _ = jax.lax.scan(layer, x, params["encoder"])
        return rms_norm(x, params["enc_final_norm"], cfg.norm_eps)

    # ---- decoder -----------------------------------------------------------

    def _cross_kv(self, lp, memory):
        cd = self.compute_dtype
        cfg = self.cfg
        KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        kv = memory.astype(cd) @ lp["wkv_x"].astype(cd)
        B, S = memory.shape[:2]
        k, v = jnp.split(kv, 2, axis=-1)
        return k.reshape(B, S, KV, hd), v.reshape(B, S, KV, hd)

    def _cross_attend(self, lp, x, k_mem, v_mem):
        cd = self.compute_dtype
        cfg = self.cfg
        H, hd = cfg.n_heads, cfg.resolved_head_dim
        B, S = x.shape[:2]
        q = (x.astype(cd) @ lp["wq_x"].astype(cd)).reshape(B, S, H, hd)
        out = attn_lib.attention(q, k_mem, v_mem, impl="blockwise" if S > 1 else "naive", causal=False)
        return out.reshape(B, S, H * hd) @ lp["wo_x"].astype(cd)

    def _decode_stack(self, params, x, memory, *, positions, collect_cache):
        cfg = self.cfg
        cd = self.compute_dtype

        def layer(carry, lp):
            x, = carry
            h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            a, kv = attn_block_apply(cfg, lp, h, positions=positions, compute_dtype=cd)
            x = x + a
            h = rms_norm(x, lp["xattn_norm"], cfg.norm_eps)
            k_mem, v_mem = self._cross_kv(lp, memory)
            x = x + self._cross_attend(lp, h, k_mem, v_mem)
            h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            x = x + mlp_apply(lp, h, cd)
            ys = (kv, (k_mem, v_mem)) if collect_cache else None
            return (x,), ys

        if cfg.remat != "none":
            layer = jax.checkpoint(layer, prevent_cse=False)
        (x,), caches = jax.lax.scan(layer, (x,), params["decoder"])
        return rms_norm(x, params["final_norm"], cfg.norm_eps), caches

    # ---- public API ----------------------------------------------------------

    def loss(self, params, batch):
        cfg = self.cfg
        memory = self._encode(params, batch["frame_embeds"])
        tokens = batch["tokens"]
        x = embed_lookup(params["embed"], tokens).astype(self.compute_dtype)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        x, _ = self._decode_stack(params, x, memory, positions=positions, collect_cache=False)
        targets, mask = shift_targets(tokens, batch.get("mask"))
        tot, cnt = chunked_cross_entropy(x, params["lm_head"].T, targets, mask, vocab_size=cfg.vocab_size)
        loss = tot / jnp.maximum(cnt, 1.0)
        return loss, {"ce_loss": loss, "tokens": cnt}

    def prefill(self, params, batch):
        memory = self._encode(params, batch["frame_embeds"])
        tokens = batch["tokens"]
        x = embed_lookup(params["embed"], tokens).astype(self.compute_dtype)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        x, caches = self._decode_stack(params, x, memory, positions=positions, collect_cache=True)
        (k, v), (k_mem, v_mem) = caches
        logits = x[:, -1:].astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
        return logits, {"k": k, "v": v, "k_mem": k_mem, "v_mem": v_mem}

    def decode(self, params, cache, batch):
        cfg = self.cfg
        cd = self.compute_dtype
        positions = batch["positions"]
        x = embed_lookup(params["embed"], batch["tokens"]).astype(cd)

        def layer(carry, inp):
            x, = carry
            lp, k_c, v_c, k_mem, v_mem = inp
            h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            a, (k_c, v_c) = attn_block_decode(cfg, lp, h, k_c, v_c, positions=positions, compute_dtype=cd)
            x = x + a
            h = rms_norm(x, lp["xattn_norm"], cfg.norm_eps)
            x = x + self._cross_attend(lp, h, k_mem, v_mem)
            h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            x = x + mlp_apply(lp, h, cd)
            return (x,), (k_c, v_c)

        (x,), (k, v) = jax.lax.scan(
            layer, (x,), (params["decoder"], cache["k"], cache["v"], cache["k_mem"], cache["v_mem"])
        )
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
        return logits, {"k": k, "v": v, "k_mem": cache["k_mem"], "v_mem": cache["v_mem"]}

    # ---- input structs ---------------------------------------------------------

    def input_specs(self, shape: ShapeConfig) -> dict:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        if shape.kind == "decode":
            return {
                "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                "positions": jax.ShapeDtypeStruct((B,), jnp.int32),
            }
        half = S // 2
        return {
            "frame_embeds": jax.ShapeDtypeStruct((B, half, cfg.d_model), jnp.bfloat16),
            "tokens": jax.ShapeDtypeStruct((B, half), jnp.int32),
        }

    def input_axes(self, shape: ShapeConfig) -> dict:
        if shape.kind == "decode":
            return {"tokens": ("batch", None), "positions": ("batch",)}
        return {"frame_embeds": ("batch", "seq", None), "tokens": ("batch", "seq")}

    def cache_struct(self, shape: ShapeConfig):
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        half = S // 2
        kv = jax.ShapeDtypeStruct((cfg.n_layers, B, half, cfg.n_kv_heads, cfg.resolved_head_dim), jnp.bfloat16)
        return {"k": kv, "v": kv, "k_mem": kv, "v_mem": kv}

    def cache_axes(self, shape: ShapeConfig):
        ax = ("layers", "batch", "cache_seq", None, None)
        return {"k": ax, "v": ax, "k_mem": ax, "v_mem": ax}
