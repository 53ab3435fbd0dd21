"""Logical-axis -> PartitionSpec rules (divisibility-checked).

Params and activations carry *logical* axis names; this module
maps them onto the mesh:

* tensor-parallel names ("vocab", "mlp", "qkv", "heads", "kv", "experts")
  shard on the "model" axis;
* "batch" shards on ("pod","data") (greedily trimmed so the dim divides);
* "seq" (train/prefill activations) shards on "model" (sequence parallelism —
  no head-count divisibility constraints);
* "cache_seq" (decode KV caches) shards on "model", and additionally takes
  the "data" axis when the batch is too small to use it (long-context decode, B=1);
* ZeRO: every parameter additionally shards its largest unmapped dim over
  ("pod","data") when divisible (optimizer state inherits param shardings).

jax rejects non-divisible shardings, so every mapping is checked against the
actual dim and silently falls back to replication when it does not divide.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

TENSOR_AXES = ("vocab", "mlp", "qkv", "heads", "kv", "experts")


def _axis_size(mesh: Mesh, axes: Sequence[str]) -> int:
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def _fits(dim: int, mesh: Mesh, axes: Sequence[str]) -> bool:
    s = _axis_size(mesh, axes)
    return s > 1 and dim % s == 0


@dataclass
class ShardingRules:
    """Maps logical axis names to mesh axes for one (mesh, workload shape)."""

    mesh: Mesh
    batch_axes: tuple[str, ...] = ()
    zero: bool = True  # FSDP/ZeRO-shard params over the batch axes
    kind: str = "train"  # "train" | "prefill" | "decode"

    @classmethod
    def for_shape(cls, mesh: Mesh, *, kind: str, global_batch: int, zero: bool = True) -> "ShardingRules":
        dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
        # greedily trim the batch axes until the global batch divides
        batch_axes = dp
        while batch_axes and global_batch % _axis_size(mesh, batch_axes) != 0:
            batch_axes = batch_axes[1:]
        return cls(mesh=mesh, batch_axes=batch_axes, zero=zero, kind=kind)

    # -- logical name -> candidate mesh axes --------------------------------

    def _map_name(self, name: str | None, dim: int) -> Any:
        if name is None or name == "layers":
            return None
        if name in TENSOR_AXES:
            return "model" if _fits(dim, self.mesh, ("model",)) else None
        if name == "embed":
            return None  # ZeRO may take it for params
        if name in ("batch", "moe_groups"):
            return self.batch_axes if _fits(dim, self.mesh, self.batch_axes) else None
        if name == "seq":
            return "model" if _fits(dim, self.mesh, ("model",)) else None
        if name == "cache_seq":
            unused = tuple(
                a for a in ("pod", "data") if a in self.mesh.shape and a not in self.batch_axes
            )
            cand = unused + ("model",)
            if _fits(dim, self.mesh, cand):
                return cand
            return "model" if _fits(dim, self.mesh, ("model",)) else None
        raise ValueError(f"unknown logical axis {name!r}")

    def spec(self, axes: Sequence[str | None], shape: Sequence[int], *, is_param: bool = False) -> P:
        entries: list[Any] = []
        used: set[str] = set()
        for name, dim in zip(axes, shape):
            m = self._map_name(name, dim)
            if isinstance(m, tuple) and not m:
                m = None
            if m is not None:
                flat = (m,) if isinstance(m, str) else tuple(m)
                if used & set(flat):
                    m = None  # a mesh axis may appear once per spec
                else:
                    used.update(flat)
            entries.append(m)
        if is_param and self.zero:
            entries = self._apply_zero(entries, axes, shape, used)
        while entries and entries[-1] is None:
            entries.pop()
        # 1-tuples mean the same partitioning as their bare axis name, but the
        # pinned jax's PartitionSpec compares them unequal — normalize
        entries = [e[0] if isinstance(e, tuple) and len(e) == 1 else e for e in entries]
        return P(*entries)

    def _apply_zero(self, entries, axes, shape, used) -> list:
        if "vocab" in axes:
            # embedding / lm_head stay vocab-sharded only: the vocab-parallel
            # CE (runtime/losses.py) consumes them directly per-shard
            return entries
        zero_axes = tuple(
            a for a in ("pod", "data") if a in self.mesh.shape and a not in used
        )
        if not zero_axes:
            return entries
        # largest unmapped dim that divides by the full zero-axis group
        order = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in order:
            if entries[i] is not None or axes[i] == "layers":
                continue
            for cand in (zero_axes, zero_axes[-1:]):
                if _fits(shape[i], self.mesh, cand):
                    entries[i] = cand if len(cand) > 1 else cand[0]
                    return entries
        return entries

    # -- tree-level helpers ---------------------------------------------------

    def shardings(self, axes_tree: Any, struct_tree: Any, *, is_param: bool = False) -> Any:
        def one(axes, struct):
            return NamedSharding(self.mesh, self.spec(axes, struct.shape, is_param=is_param))

        return jax.tree.map(
            one, axes_tree, struct_tree, is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)
        )


def param_shardings(model, mesh: Mesh, *, zero: bool = True) -> Any:
    rules = ShardingRules(mesh=mesh, batch_axes=(), zero=zero)
    # params don't depend on the workload shape; batch axes only matter for ZeRO
    rules.batch_axes = ()
    return rules.shardings(model.param_axes(), model.param_struct(), is_param=True)


def replicated(mesh: Mesh, tree: Any) -> Any:
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


# ---------------------------------------------------------------------------
# activation-constraint context: models call ``constrain(x, axes)`` with
# logical names; it is a no-op unless a step builder installed rules.
# ---------------------------------------------------------------------------

import contextlib
import threading

_CTX = threading.local()


@contextlib.contextmanager
def activation_rules(rules: "ShardingRules | None"):
    prev = getattr(_CTX, "rules", None)
    _CTX.rules = rules
    try:
        yield
    finally:
        _CTX.rules = prev


def constrain(x: jax.Array, axes: Sequence[str | None]) -> jax.Array:
    """Attach a GSPMD sharding constraint using logical axis names (no-op
    outside an ``activation_rules`` context)."""
    rules: ShardingRules | None = getattr(_CTX, "rules", None)
    if rules is None:
        return x
    spec = rules.spec(axes, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))
