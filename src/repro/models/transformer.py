"""Decoder-only transformer LM: dense, MoE and VLM (stub frontend) families.

Layers are stacked along a leading "layers" axis and applied with
``lax.scan`` (O(1)-in-depth HLO; production compile times). Attention blocks
are reusable by the enc-dec and hybrid families.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import attention as attn_lib
from repro.models import ffn, moe
from repro.models.base import BaseModel
from repro.models.common import embed_lookup, ParamSpec, apply_rope, chunked_cross_entropy, rms_norm, shift_targets


# ---------------------------------------------------------------------------
# attention block (shared with encdec / zamba)
# ---------------------------------------------------------------------------


def attn_block_specs(cfg: ArchConfig, n_layers: int | None, d_in: int | None = None) -> dict:
    d = d_in or cfg.d_model
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.param_dtype)
    lead = () if n_layers is None else (n_layers,)
    lax_ = () if n_layers is None else ("layers",)
    specs = {
        "wqkv": ParamSpec(lead + (d, (H + 2 * KV) * hd), lax_ + ("embed", "qkv"), dt),
        "wo": ParamSpec(lead + (H * hd, cfg.d_model), lax_ + ("heads", "embed"), dt),
    }
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec(lead + (hd,), lax_ + (None,), jnp.float32, init="ones")
        specs["k_norm"] = ParamSpec(lead + (hd,), lax_ + (None,), jnp.float32, init="ones")
    return specs


def _split_qkv(cfg: ArchConfig, qkv: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    B, S = qkv.shape[:2]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q, k, v = jnp.split(qkv, [H * hd, (H + KV) * hd], axis=-1)
    return (
        q.reshape(B, S, H, hd),
        k.reshape(B, S, KV, hd),
        v.reshape(B, S, KV, hd),
    )


def _qk_norm(cfg: ArchConfig, p: dict, q: jax.Array, k: jax.Array):
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k


def attn_block_apply(
    cfg: ArchConfig,
    p: dict,
    x: jax.Array,
    *,
    positions: jax.Array,
    compute_dtype,
    causal: bool = True,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Full-sequence attention (train / prefill). Returns (out, (k, v))."""
    cd = compute_dtype
    qkv = x.astype(cd) @ p["wqkv"].astype(cd)
    q, k, v = _split_qkv(cfg, qkv)
    q, k = _qk_norm(cfg, p, q, k)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_pct)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_pct)
    out = attn_lib.attention(
        q, k, v,
        impl=cfg.attention_impl,
        causal=causal,
        block_q=cfg.attention_block_q,
        block_kv=cfg.attention_block_kv,
    )
    B, S = x.shape[:2]
    out = out.reshape(B, S, -1) @ p["wo"].astype(cd)
    return out, (k, v)


def attn_block_decode(
    cfg: ArchConfig,
    p: dict,
    x: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    *,
    positions: jax.Array,
    compute_dtype,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Single-token attention against the cache. ``x``: (B,1,d)."""
    cd = compute_dtype
    qkv = x.astype(cd) @ p["wqkv"].astype(cd)
    q, k, v = _split_qkv(cfg, qkv)
    q, k = _qk_norm(cfg, p, q, k)
    pos = positions[:, None]  # (B,1)
    q = apply_rope(q, pos, cfg.rope_theta, cfg.rope_pct)
    k = apply_rope(k, pos, cfg.rope_theta, cfg.rope_pct)
    k_cache = attn_lib.update_cache(k_cache, k, positions)
    v_cache = attn_lib.update_cache(v_cache, v, positions)
    out = attn_lib.decode_attention(q, k_cache, v_cache, positions=positions)
    out = out.reshape(x.shape[0], 1, -1) @ p["wo"].astype(cd)
    return out, (k_cache, v_cache)


# ---------------------------------------------------------------------------
# decoder-only LM
# ---------------------------------------------------------------------------


class DecoderLM(BaseModel):
    """Dense / MoE / VLM decoder-only language model."""

    SUPPORTS_PAGED = True

    @property
    def is_moe(self) -> bool:
        return bool(self.cfg.n_experts)

    @property
    def is_vlm(self) -> bool:
        return self.cfg.frontend == "vision"

    # ---- specs -----------------------------------------------------------

    def param_specs(self) -> dict:
        cfg = self.cfg
        L = cfg.n_layers
        dt = self.param_dtype
        d = cfg.d_model
        layers: dict[str, Any] = {
            "attn_norm": ParamSpec((L, d), ("layers", "embed"), jnp.float32, init="ones"),
            "mlp_norm": ParamSpec((L, d), ("layers", "embed"), jnp.float32, init="ones"),
            **attn_block_specs(cfg, L),
        }
        if self.is_moe:
            layers.update(moe.moe_specs(cfg, L))
        else:
            layers.update(ffn.mlp_specs(d, cfg.d_ff, L, dt, gated=cfg.gated_mlp))
        specs = {
            "embed": ParamSpec((cfg.padded_vocab, d), ("vocab", "embed"), dt, init="normal"),
            "final_norm": ParamSpec((d,), ("embed",), jnp.float32, init="ones"),
            "layers": layers,
        }
        if not cfg.tie_embeddings:
            specs["lm_head"] = ParamSpec((d, cfg.padded_vocab), ("embed", "vocab"), dt)
        if self.is_vlm:
            specs["vision_proj"] = ParamSpec((d, d), ("embed", None), dt)
        return specs

    def expert_param_count(self) -> int:
        if not self.is_moe:
            return 0
        cfg = self.cfg
        return cfg.n_layers * cfg.n_experts * 3 * cfg.d_model * cfg.d_ff

    def _head(self, params: dict) -> jax.Array:
        """(V_pad, d) output projection."""
        if self.cfg.tie_embeddings:
            return params["embed"]
        return params["lm_head"].T

    # ---- forward ---------------------------------------------------------

    def _embed_inputs(self, params: dict, batch: dict) -> jax.Array:
        x = embed_lookup(params["embed"], batch["tokens"]).astype(self.compute_dtype)
        if self.is_vlm:
            patches = batch["patch_embeds"].astype(self.compute_dtype)
            patches = patches @ params["vision_proj"].astype(self.compute_dtype)
            x = jnp.concatenate([patches, x], axis=1)
        return x

    def _layer_fn(self, collect_cache: bool):
        cfg = self.cfg
        cd = self.compute_dtype

        from repro.runtime.sharding import constrain

        def layer(carry, lp):
            x, aux, positions = carry
            x = constrain(x, ("batch", "seq", None))
            h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            a, kv = attn_block_apply(cfg, lp, h, positions=positions, compute_dtype=cd)
            x = x + a
            h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            if self.is_moe:
                m, layer_aux = moe.moe_apply(lp, h, cfg, cd)
                aux = aux + layer_aux
            else:
                m = ffn.mlp_apply(lp, h, cd)
            x = x + m
            ys = kv if collect_cache else None
            return (x, aux, positions), ys

        if cfg.remat != "none":
            policy = None if cfg.remat == "full" else jax.checkpoint_policies.checkpoint_dots
            layer = jax.checkpoint(layer, policy=policy, prevent_cse=False)
        return layer

    def _forward(self, params: dict, batch: dict, *, collect_cache: bool):
        x = self._embed_inputs(params, batch)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        layer = self._layer_fn(collect_cache)
        (x, aux, _), caches = jax.lax.scan(layer, (x, jnp.float32(0.0), positions), params["layers"])
        x = rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        return x, aux, caches

    # ---- public API ------------------------------------------------------

    def loss(self, params: dict, batch: dict) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        x, aux, _ = self._forward(params, batch, collect_cache=False)
        tokens = batch["tokens"]
        targets, mask = shift_targets(tokens, batch.get("mask"))
        if self.is_vlm:  # text hidden states start at patch offset - 1
            P = x.shape[1] - tokens.shape[1]
            x = x[:, P :]
        tot, cnt = chunked_cross_entropy(
            x, self._head(params), targets, mask, vocab_size=cfg.vocab_size
        )
        loss = tot / jnp.maximum(cnt, 1.0)
        metrics = {"ce_loss": loss, "tokens": cnt}
        if self.is_moe:
            aux = aux / cfg.n_layers
            metrics["aux_loss"] = aux
            loss = loss + 0.01 * aux
        return loss, metrics

    def prefill(self, params: dict, batch: dict) -> tuple[jax.Array, Any]:
        x, _, (k, v) = self._forward(params, batch, collect_cache=True)
        last = batch.get("last_pos")
        if last is None:
            xs = x[:, -1:]
        else:
            # variable-length prompts right-padded to a bucket: the logits
            # must come from the true last token, not the padding tail
            xs = x[jnp.arange(x.shape[0]), last][:, None]
        logits = xs.astype(jnp.float32) @ self._head(params).T.astype(jnp.float32)
        cache = {"k": k, "v": v}  # (L, B, S, KV, hd)
        return logits, cache

    def decode(self, params: dict, cache: Any, batch: dict) -> tuple[jax.Array, Any]:
        cfg = self.cfg
        cd = self.compute_dtype
        tokens, positions = batch["tokens"], batch["positions"]
        x = embed_lookup(params["embed"], tokens).astype(cd)  # (B,1,d)

        def layer(carry, inp):
            x, positions = carry
            lp, k_c, v_c = inp
            h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            a, (k_c, v_c) = attn_block_decode(
                cfg, lp, h, k_c, v_c, positions=positions, compute_dtype=cd
            )
            x = x + a
            h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            if self.is_moe:
                m, _ = moe.moe_apply(lp, h, cfg, cd)
            else:
                m = ffn.mlp_apply(lp, h, cd)
            return (x + m, positions), (k_c, v_c)

        (x, _), (k, v) = jax.lax.scan(layer, (x, positions), (params["layers"], cache["k"], cache["v"]))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = x.astype(jnp.float32) @ self._head(params).T.astype(jnp.float32)
        return logits, {"k": k, "v": v}

    # ---- input structs ---------------------------------------------------

    def input_specs(self, shape: ShapeConfig) -> dict:
        B, S = shape.global_batch, shape.seq_len
        if shape.kind == "decode":
            return {
                "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                "positions": jax.ShapeDtypeStruct((B,), jnp.int32),
            }
        if self.is_vlm:
            P = self.cfg.n_patches
            return {
                "tokens": jax.ShapeDtypeStruct((B, S - P), jnp.int32),
                "patch_embeds": jax.ShapeDtypeStruct((B, P, self.cfg.d_model), jnp.bfloat16),
            }
        return {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}

    def input_axes(self, shape: ShapeConfig) -> dict:
        if shape.kind == "decode":
            return {"tokens": ("batch", None), "positions": ("batch",)}
        axes = {"tokens": ("batch", "seq")}
        if self.is_vlm:
            axes["patch_embeds"] = ("batch", "seq", None)
        return axes

    def cache_struct(self, shape: ShapeConfig) -> Any:
        cfg = self.cfg
        L, B, S = cfg.n_layers, shape.global_batch, shape.seq_len
        kv = jax.ShapeDtypeStruct((L, B, S, cfg.n_kv_heads, cfg.resolved_head_dim), jnp.bfloat16)
        return {"k": kv, "v": kv}

    def cache_axes(self, shape: ShapeConfig) -> Any:
        ax = ("layers", "batch", "cache_seq", None, None)
        return {"k": ax, "v": ax}
