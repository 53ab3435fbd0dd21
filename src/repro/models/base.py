"""Model interface shared by every architecture family."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models.common import SpecTree, init_params, spec_axes, spec_struct


class BaseModel:
    """A model = param specs + pure functions (loss / prefill / decode).

    Subclasses implement ``param_specs``, ``loss``, ``prefill``,
    ``decode`` and the shape-struct providers the step builders lower
    against.
    """

    #: model family supports the paged-KV serving path (runtime/steps.py
    #: paged builders): prefill honours ``batch["last_pos"]`` and its cache
    #: is the standard (L, B, S, KV, hd) {"k","v"} tree
    SUPPORTS_PAGED = False

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.compute_dtype = jnp.dtype(cfg.compute_dtype)
        self.param_dtype = jnp.dtype(cfg.param_dtype)

    # ---- params ----------------------------------------------------------

    def param_specs(self) -> SpecTree:
        raise NotImplementedError

    def param_struct(self) -> Any:
        return spec_struct(self.param_specs())

    def param_axes(self) -> Any:
        return spec_axes(self.param_specs())

    def init(self, key: jax.Array) -> Any:
        return init_params(self.param_specs(), key)

    def expert_param_count(self) -> int:
        """Parameters living on the routed-expert path (MoE accounting)."""
        return 0

    # ---- compute ---------------------------------------------------------

    def loss(self, params: Any, batch: dict) -> tuple[jax.Array, dict]:
        """Mean next-token loss + metrics dict for one (micro)batch."""
        raise NotImplementedError

    def prefill(self, params: Any, batch: dict) -> tuple[jax.Array, Any]:
        """Process the full prompt; returns (last-token logits, cache)."""
        raise NotImplementedError

    def decode(self, params: Any, cache: Any, batch: dict) -> tuple[jax.Array, Any]:
        """One decode step; returns (logits, updated cache)."""
        raise NotImplementedError

    # ---- input structs -----------------------------------------------------

    def input_specs(self, shape: ShapeConfig) -> dict:
        """ShapeDtypeStruct stand-ins for every model input of this shape."""
        raise NotImplementedError

    def input_axes(self, shape: ShapeConfig) -> dict:
        """Logical axes for each input (parallel to ``input_specs``)."""
        raise NotImplementedError

    def cache_struct(self, shape: ShapeConfig) -> Any:
        """ShapeDtypeStruct tree for the decode cache at this shape."""
        raise NotImplementedError

    def cache_axes(self, shape: ShapeConfig) -> Any:
        raise NotImplementedError
