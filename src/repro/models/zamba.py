"""Zamba2-style hybrid: Mamba2 backbone + weight-tied shared attention block.

The shared transformer block (attention + MLP, one set of weights) is applied
before every ``shared_block_every``-th Mamba2 layer, consuming
``concat([x, x0])`` (current stream + original embeddings) as in Zamba2 —
the concat restores information the SSM stream may have compressed away.
Per-invocation LoRA adapters from the released model are omitted;
everything else follows the published layout.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import mamba2
from repro.models.base import BaseModel
from repro.models.common import embed_lookup, ParamSpec, chunked_cross_entropy, rms_norm, shift_targets
from repro.models.ffn import mlp_apply, mlp_specs
from repro.models.transformer import attn_block_apply, attn_block_decode, attn_block_specs


class ZambaLM(BaseModel):
    @property
    def n_sites(self) -> int:
        return math.ceil(self.cfg.n_layers / self.cfg.shared_block_every)

    def _groups(self) -> list[tuple[int, int]]:
        """[(start, end)] mamba layer index ranges, one per shared-block site."""
        k = self.cfg.shared_block_every
        L = self.cfg.n_layers
        return [(s, min(s + k, L)) for s in range(0, L, k)]

    def param_specs(self) -> dict:
        cfg = self.cfg
        d = cfg.d_model
        dt = self.param_dtype
        shared = {
            "attn_norm": ParamSpec((2 * d,), ("embed",), jnp.float32, init="ones"),
            "mlp_norm": ParamSpec((d,), ("embed",), jnp.float32, init="ones"),
            **attn_block_specs(cfg, None, d_in=2 * d),
            **mlp_specs(d, cfg.d_ff, None, dt),
        }
        return {
            "embed": ParamSpec((cfg.padded_vocab, d), ("vocab", "embed"), dt, init="normal"),
            "final_norm": ParamSpec((d,), ("embed",), jnp.float32, init="ones"),
            "lm_head": ParamSpec((d, cfg.padded_vocab), ("embed", "vocab"), dt),
            "shared": shared,
            "mamba": mamba2.mamba_specs(cfg, cfg.n_layers),
        }

    # ---- forward ---------------------------------------------------------

    def _shared_block(self, params, x, x0, *, positions):
        cfg = self.cfg
        cd = self.compute_dtype
        sp = params["shared"]
        h = jnp.concatenate([x, x0], axis=-1)
        h = rms_norm(h, sp["attn_norm"], cfg.norm_eps)
        a, kv = attn_block_apply(cfg, sp, h, positions=positions, compute_dtype=cd)
        x = x + a
        h = rms_norm(x, sp["mlp_norm"], cfg.norm_eps)
        return x + mlp_apply(sp, h, cd), kv

    def _shared_block_decode(self, params, x, x0, k_c, v_c, *, positions):
        cfg = self.cfg
        cd = self.compute_dtype
        sp = params["shared"]
        h = jnp.concatenate([x, x0], axis=-1)
        h = rms_norm(h, sp["attn_norm"], cfg.norm_eps)
        a, (k_c, v_c) = attn_block_decode(cfg, sp, h, k_c, v_c, positions=positions, compute_dtype=cd)
        x = x + a
        h = rms_norm(x, sp["mlp_norm"], cfg.norm_eps)
        return x + mlp_apply(sp, h, cd), (k_c, v_c)

    def _forward(self, params, tokens, *, collect_cache: bool):
        cfg = self.cfg
        cd = self.compute_dtype
        x = embed_lookup(params["embed"], tokens).astype(cd)
        x0 = x
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

        def mamba_layer(x, lp):
            out, state = mamba2.mamba_apply(cfg, lp, x, None, compute_dtype=cd, chunked=True)
            return x + out, state if collect_cache else None

        if cfg.remat != "none":
            policy = None if cfg.remat == "full" else jax.checkpoint_policies.checkpoint_dots
            mamba_layer = jax.checkpoint(mamba_layer, policy=policy, prevent_cse=False)

        shared_block = self._shared_block
        if cfg.remat != "none":
            # the 7 shared-attention sites are unrolled (weight-tied), so
            # each needs its own remat scope or their residuals all coexist
            shared_block = jax.checkpoint(
                shared_block, prevent_cse=False, static_argnums=(), policy=None
            )

        kvs, mamba_states = [], []
        for (s, e) in self._groups():
            x, kv = shared_block(params, x, x0, positions=positions)
            lp_g = jax.tree.map(lambda a: a[s:e], params["mamba"])
            x, st = jax.lax.scan(mamba_layer, x, lp_g)
            kvs.append(kv)
            mamba_states.append(st)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)

        cache = None
        if collect_cache:
            k = jnp.stack([kv[0] for kv in kvs])  # (sites,B,S,KV,hd)
            v = jnp.stack([kv[1] for kv in kvs])
            mamba_state = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *mamba_states)
            cache = {"k": k, "v": v, "mamba": mamba_state}
        return x, cache

    # ---- public API ------------------------------------------------------

    def loss(self, params, batch):
        tokens = batch["tokens"]
        x, _ = self._forward(params, tokens, collect_cache=False)
        targets, mask = shift_targets(tokens, batch.get("mask"))
        tot, cnt = chunked_cross_entropy(x, params["lm_head"].T, targets, mask, vocab_size=self.cfg.vocab_size)
        loss = tot / jnp.maximum(cnt, 1.0)
        return loss, {"ce_loss": loss, "tokens": cnt}

    def prefill(self, params, batch):
        x, cache = self._forward(params, batch["tokens"], collect_cache=True)
        logits = x[:, -1:].astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
        return logits, cache

    def decode(self, params, cache, batch):
        cfg = self.cfg
        cd = self.compute_dtype
        positions = batch["positions"]
        x = embed_lookup(params["embed"], batch["tokens"]).astype(cd)
        x0 = x

        def mamba_layer(x, inp):
            lp, conv, ssd = inp
            out, state = mamba2.mamba_apply(
                cfg, lp, x, {"conv": conv, "ssd": ssd}, compute_dtype=cd, chunked=False
            )
            return x + out, (state["conv"], state["ssd"])

        ks, vs, convs, ssds = [], [], [], []
        for i, (s, e) in enumerate(self._groups()):
            x, (k_c, v_c) = self._shared_block_decode(
                params, x, x0, cache["k"][i], cache["v"][i], positions=positions
            )
            lp_g = jax.tree.map(lambda a: a[s:e], params["mamba"])
            conv_g, ssd_g = cache["mamba"]["conv"][s:e], cache["mamba"]["ssd"][s:e]
            x, (conv_n, ssd_n) = jax.lax.scan(mamba_layer, x, (lp_g, conv_g, ssd_g))
            ks.append(k_c), vs.append(v_c), convs.append(conv_n), ssds.append(ssd_n)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
        new_cache = {
            "k": jnp.stack(ks),
            "v": jnp.stack(vs),
            "mamba": {
                "conv": jnp.concatenate(convs, axis=0),
                "ssd": jnp.concatenate(ssds, axis=0),
            },
        }
        return logits, new_cache

    # ---- input structs ---------------------------------------------------

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
        B, S = shape.global_batch, shape.seq_len
        kv = jax.ShapeDtypeStruct(
            (self.n_sites, B, S, cfg.n_kv_heads, cfg.resolved_head_dim), jnp.bfloat16
        )
        return {"k": kv, "v": kv, "mamba": mamba2.mamba_state_struct(cfg, cfg.n_layers, B)}

    def cache_axes(self, shape: ShapeConfig):
        ax = ("layers", "batch", "cache_seq", None, None)
        return {"k": ax, "v": ax, "mamba": mamba2.mamba_state_axes()}
