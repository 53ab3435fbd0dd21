"""Shared model building blocks: param specs, norms, RoPE, embeddings, loss.

Parameters are plain nested dicts of arrays. Every leaf is declared through a
:class:`ParamSpec` carrying *logical axis names*; ``runtime.sharding`` maps
those names onto mesh axes. The same spec tree serves real initialization
(smoke tests, examples) and allocation-free ``ShapeDtypeStruct`` trees
(lowering a step without data).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

Axes = tuple  # tuple[str | None, ...]


@dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    axes: Axes  # logical axis name per dim (None = replicated dim)
    dtype: Any = jnp.float32
    init: str = "fan_in"  # "fan_in" | "normal" | "zeros" | "ones" | "small"
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)

    def struct(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype)

    def initialize(self, key: jax.Array) -> jax.Array:
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        if self.init == "normal":
            std = 0.02 * self.scale
        elif self.init == "small":
            std = 1e-3 * self.scale
        else:  # fan_in
            fan_in = self.shape[-2] if len(self.shape) >= 2 else self.shape[-1]
            std = self.scale / math.sqrt(max(fan_in, 1))
        x = jax.random.normal(key, self.shape, jnp.float32) * std
        return x.astype(self.dtype)


SpecTree = Any  # nested dict[str, ParamSpec]


def spec_struct(specs: SpecTree) -> Any:
    return jax.tree.map(lambda s: s.struct(), specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def spec_axes(specs: SpecTree) -> Any:
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def init_params(specs: SpecTree, key: jax.Array) -> Any:
    leaves, treedef = jax.tree.flatten(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [s.initialize(k) for s, k in zip(leaves, keys)])


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def group_norm(x: jax.Array, n_groups: int, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    """GroupNorm over the last dim split into ``n_groups`` (RWKV wkv output)."""
    dt = x.dtype
    *lead, d = x.shape
    g = x.astype(jnp.float32).reshape(*lead, n_groups, d // n_groups)
    mu = jnp.mean(g, axis=-1, keepdims=True)
    var = jnp.mean((g - mu) ** 2, axis=-1, keepdims=True)
    g = (g - mu) * jax.lax.rsqrt(var + eps)
    x = g.reshape(*lead, d)
    return (x * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# rotary position embeddings (GPT-NeoX half-rotation convention)
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float, rope_pct: float = 1.0) -> jax.Array:
    """Inverse frequencies for the rotated fraction of the head dim."""
    rot = int(head_dim * rope_pct) // 2 * 2
    return 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float, rope_pct: float = 1.0) -> jax.Array:
    """``x``: (..., seq, heads, head_dim); ``positions``: broadcastable (..., seq)."""
    dt = x.dtype
    hd = x.shape[-1]
    rot = int(hd * rope_pct) // 2 * 2
    inv = rope_frequencies(hd, theta, rope_pct)  # (rot/2,)
    ang = positions[..., :, None, None].astype(jnp.float32) * inv  # (..., seq, 1, rot/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    xr, xp = x[..., :rot].astype(jnp.float32), x[..., rot:]
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2 :]
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rotated.astype(dt), xp], axis=-1) if rot < hd else rotated.astype(dt)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


def chunked_cross_entropy(
    x: jax.Array,
    embedding: jax.Array,
    targets: jax.Array,
    mask: jax.Array,
    *,
    vocab_size: int,
    chunk: int = 1024,
) -> tuple[jax.Array, jax.Array]:
    """Next-token CE without materializing full (B,S,V) logits.

    ``x``: (B,S,D) final hidden states; ``embedding``: (V_pad, D) output head;
    ``targets``: (B,S) int32; ``mask``: (B,S) {0,1}. Scans over sequence
    chunks so peak logits memory is (B, chunk, V) regardless of sharding.
    Returns (sum_loss, sum_mask).
    """
    # sequence-parallel path: Megatron-style vocab-parallel CE via shard_map
    from repro.runtime.sharding import _CTX  # lazy to avoid import cycle

    rules = getattr(_CTX, "rules", None)
    if rules is not None and rules.mesh.shape.get("model", 1) > 1:
        from repro.runtime.losses import vocab_parallel_cross_entropy

        return vocab_parallel_cross_entropy(
            x, embedding, targets, mask.astype(jnp.float32), rules, chunk=chunk
        )

    B, S, D = x.shape
    chunk = min(chunk, S)
    while S % chunk:
        chunk -= 1
    n = S // chunk
    xs = x.reshape(B, n, chunk, D).swapaxes(0, 1)  # (n,B,c,D)
    ts = targets.reshape(B, n, chunk).swapaxes(0, 1)
    ms = mask.reshape(B, n, chunk).swapaxes(0, 1)

    emb = embedding

    def step(carry, inp):
        xc, tc, mc = inp
        logits = (xc @ emb.T.astype(xc.dtype)).astype(jnp.float32)  # (B,c,Vp)
        # padded vocab entries never appear as targets; logsumexp over the
        # padded tail is harmless (their logits train toward -inf).
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        nll = (lse - picked) * mc
        return (carry[0] + nll.sum(), carry[1] + mc.sum()), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.float32(0.0), jnp.float32(0.0)), (xs, ts, ms))
    return tot, cnt


def embed_lookup(embed: jax.Array, tokens: jax.Array) -> jax.Array:
    """Embedding lookup, vocab-parallel under an activation_rules context
    (plain ``embed[tokens]`` makes GSPMD all-gather the full table)."""
    from repro.runtime.sharding import _CTX  # lazy to avoid import cycle

    rules = getattr(_CTX, "rules", None)
    if (
        rules is not None
        and rules.mesh.shape.get("model", 1) > 1
        and tokens.ndim == 2
        and embed.shape[0] % rules.mesh.shape["model"] == 0
    ):
        from repro.runtime.losses import vocab_parallel_embed

        return vocab_parallel_embed(tokens, embed, rules)
    return embed[tokens]


def shift_targets(tokens: jax.Array, mask: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    """Standard LM shift: predict token t+1 at position t."""
    targets = jnp.concatenate([tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
    m = jnp.ones_like(tokens, dtype=jnp.float32)
    if mask is not None:
        m = m * mask.astype(jnp.float32)
    m = m.at[:, -1].set(0.0)
    return targets, m
