"""Optimizers (AdamW, Adafactor) + LR schedules + global-norm clipping.

Built from scratch (no optax in the environment). Optimizer state mirrors the
parameter tree so it inherits parameter shardings (fully-sharded states =
ZeRO); Adafactor's factored second moment drops the dominant state term for
the trillion-parameter config.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"  # "adamw" | "adafactor" | "sgd"
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    schedule: str = "cosine"  # "cosine" | "constant" | "linear"
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    moment_dtype: str = "float32"  # "float32" | "bfloat16"
    min_lr_ratio: float = 0.1
    first_moment: bool = True  # adafactor: False drops m entirely (1T configs)
    # update stacked-layer leaves one layer slice at a time (lax.map):
    # bounds optimizer f32 temporaries to 1/L of the leaf instead of ~3x leaf
    layerwise_update: bool = True


def lr_at(cfg: OptimizerConfig, step: jax.Array) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    if cfg.schedule == "constant":
        decay = 1.0
    else:
        frac = jnp.clip(
            (step - cfg.warmup_steps) / max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0
        )
        if cfg.schedule == "cosine":
            decay = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * frac))
        else:  # linear
            decay = 1.0 - (1 - cfg.min_lr_ratio) * frac
    return cfg.learning_rate * warm * decay


def _leaf_sqnorm(x: jax.Array) -> jax.Array:
    # big stacked-layer leaves: reduce one slice at a time (f32 temp / L)
    if x.ndim >= 3 and x.size >= (1 << 22):
        return jax.lax.map(lambda s: jnp.sum(jnp.square(s.astype(jnp.float32))), x).sum()
    return jnp.sum(jnp.square(x.astype(jnp.float32)))


def global_norm(tree: Any) -> jax.Array:
    return jnp.sqrt(sum(_leaf_sqnorm(x) for x in jax.tree.leaves(tree)))


def clip_by_global_norm(tree: Any, max_norm: float) -> tuple[Any, jax.Array]:
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    # scale in native dtype: no f32 copies of full leaves
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), tree), norm


def _decay_mask(path_leaf) -> bool:
    """Weight decay only on >=2D params (skip norms/biases/scalars)."""
    return len(path_leaf.shape) >= 2


# ---------------------------------------------------------------------------


class Optimizer:
    """Stateless namespace bound to a config; state is an explicit pytree."""

    def __init__(self, cfg: OptimizerConfig):
        self.cfg = cfg

    # -- state -------------------------------------------------------------

    def init(self, params: Any) -> dict:
        cfg = self.cfg
        mdt = jnp.dtype(cfg.moment_dtype)
        if cfg.name == "sgd":
            return {"step": jnp.zeros((), jnp.int32)}
        if cfg.name == "adamw":
            zeros = lambda p: jnp.zeros(p.shape, mdt)
            return {
                "step": jnp.zeros((), jnp.int32),
                "m": jax.tree.map(zeros, params),
                "v": jax.tree.map(zeros, params),
            }
        if cfg.name == "adafactor":
            def vrow(p):
                return jnp.zeros(p.shape[:-1], jnp.float32) if p.ndim >= 2 else jnp.zeros(p.shape, jnp.float32)

            def vcol(p):
                if p.ndim >= 2:
                    return jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32)
                return jnp.zeros((), jnp.float32)

            state = {
                "step": jnp.zeros((), jnp.int32),
                "v_row": jax.tree.map(vrow, params),
                "v_col": jax.tree.map(vcol, params),
            }
            if cfg.first_moment:
                state["m"] = jax.tree.map(lambda p: jnp.zeros(p.shape, mdt), params)
            return state
        raise ValueError(self.cfg.name)

    def state_struct(self, param_struct: Any) -> dict:
        return jax.eval_shape(self.init, param_struct)

    def state_axes(self, param_axes: Any) -> dict:
        """Logical axes for optimizer state, derived from param axes."""
        cfg = self.cfg
        if cfg.name == "sgd":
            return {"step": ()}
        if cfg.name == "adamw":
            return {"step": (), "m": param_axes, "v": param_axes}
        strip_last = jax.tree.map(
            lambda ax: tuple(ax[:-1]) if len(ax) >= 2 else tuple(ax),
            param_axes,
            is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
        )
        strip_snd = jax.tree.map(
            lambda ax: tuple(ax[:-2] + ax[-1:]) if len(ax) >= 2 else (),
            param_axes,
            is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
        )
        axes = {"step": (), "v_row": strip_last, "v_col": strip_snd}
        if self.cfg.first_moment:
            axes["m"] = param_axes
        return axes

    # -- update -------------------------------------------------------------

    def update(self, grads: Any, state: dict, params: Any) -> tuple[Any, dict, dict]:
        cfg = self.cfg
        step = state["step"] + 1
        lr = lr_at(cfg, step)
        # clip folded into the (layerwise) update: g32 = g.astype(f32) * gscale
        gnorm = global_norm(grads)
        gscale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-12))
        stats = {"lr": lr, "grad_norm": gnorm}

        if cfg.name == "sgd":
            new_params = jax.tree.map(
                lambda p, g: (
                    p.astype(jnp.float32) - lr * gscale * g.astype(jnp.float32)
                ).astype(p.dtype),
                params,
                grads,
            )
            return new_params, {"step": step}, stats

        if cfg.name == "adamw":
            b1, b2 = cfg.b1, cfg.b2
            c1 = 1 - b1 ** step.astype(jnp.float32)
            c2 = 1 - b2 ** step.astype(jnp.float32)

            def upd(p, g, m, v):
                g32 = g.astype(jnp.float32) * gscale
                m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g32
                v32 = b2 * v.astype(jnp.float32) + (1 - b2) * g32 * g32
                mhat, vhat = m32 / c1, v32 / c2
                delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
                if _decay_mask(p):
                    delta = delta + cfg.weight_decay * p.astype(jnp.float32)
                return (
                    (p.astype(jnp.float32) - lr * delta).astype(p.dtype),
                    m32.astype(m.dtype),
                    v32.astype(v.dtype),
                )

            out = jax.tree.map(self._leafwise(upd), params, grads, state["m"], state["v"])
            pick = lambda i: jax.tree.map(lambda t: t[i], out, is_leaf=lambda x: isinstance(x, tuple))
            return pick(0), {"step": step, "m": pick(1), "v": pick(2)}, stats

        if cfg.name == "adafactor":
            b2t = 1.0 - (step.astype(jnp.float32) ** -0.8)
            use_m = cfg.first_moment

            def upd(p, g, vr, vc, m=None):
                g32 = g.astype(jnp.float32) * gscale
                g2 = g32 * g32 + 1e-30
                if p.ndim >= 2:
                    vr32 = b2t * vr + (1 - b2t) * g2.mean(axis=-1)
                    vc32 = b2t * vc + (1 - b2t) * g2.mean(axis=-2)
                    denom = jnp.maximum(vr32.mean(axis=-1, keepdims=True), 1e-30)
                    vhat = (vr32[..., :, None] / denom[..., None]) * vc32[..., None, :]
                else:
                    vr32 = b2t * vr + (1 - b2t) * g2
                    vc32 = vc
                    vhat = vr32
                u = g32 / jnp.sqrt(vhat + cfg.eps)
                # update clipping (Adafactor §7)
                rms_u = jnp.sqrt(jnp.mean(u * u) + 1e-30)
                u = u / jnp.maximum(1.0, rms_u)
                if use_m:
                    u = cfg.b1 * m.astype(jnp.float32) + (1 - cfg.b1) * u
                    new_m = u.astype(m.dtype)
                delta = u
                if _decay_mask(p):
                    delta = delta + cfg.weight_decay * p.astype(jnp.float32)
                new_p = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
                return (new_p, vr32, vc32, new_m) if use_m else (new_p, vr32, vc32)

            if use_m:
                out = jax.tree.map(
                    self._leafwise(upd), params, grads, state["v_row"], state["v_col"], state["m"]
                )
            else:
                out = jax.tree.map(self._leafwise(upd), params, grads, state["v_row"], state["v_col"])
            pick = lambda i: jax.tree.map(lambda t: t[i], out, is_leaf=lambda x: isinstance(x, tuple))
            new_state = {"step": step, "v_row": pick(1), "v_col": pick(2)}
            if use_m:
                new_state["m"] = pick(3)
            return pick(0), new_state, stats

        raise ValueError(cfg.name)

    def _leafwise(self, upd):
        """Wrap a per-leaf update to run one leading-dim slice at a time for
        big stacked-layer leaves (bounds f32 temporaries to leaf/L)."""
        if not self.cfg.layerwise_update:
            return upd

        def wrapped(p, g, *rest):
            big = p.ndim >= 3 and p.shape[0] >= 8 and p.size >= (1 << 22)
            consistent = all(
                r.ndim >= 1 and r.shape[:1] == p.shape[:1] for r in rest
            )
            if big and g.shape == p.shape and consistent:
                return jax.lax.map(lambda args: upd(*args), (p, g, *rest))
            return upd(p, g, *rest)

        return wrapped
