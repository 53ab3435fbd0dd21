"""Jitted wrapper for the flash-attention kernel ((B,S,H,hd) layout in/out).

``interpret`` states the kernel mode; ``None`` derives it in one place
(:func:`repro.kernels.kernel_interpret`): native on a TPU, interpret mode
only where the CPU tests run."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import kernel_interpret
from repro.kernels.attention.kernel import flash_attention_pallas
from repro.kernels.attention.ref import attention_ref

#: VMEM budget guard: K+V panels per program must fit comfortably
_VMEM_PANEL_LIMIT = 8 * 1024 * 1024


def flash_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Skv, KV, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 256,
    block_kv: int = 512,
    use_kernel: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    qt = q.swapaxes(1, 2)  # (B, H, Sq, hd)
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)
    if use_kernel:
        panel = kt.shape[2] * kt.shape[3] * kt.dtype.itemsize * 2
        if panel > _VMEM_PANEL_LIMIT:
            raise ValueError(
                f"KV panel {panel}B exceeds VMEM budget; shard the sequence "
                "(runtime/sharded_attention.py) before calling the kernel"
            )
        out = flash_attention_pallas(
            qt, kt, vt, causal=causal, block_q=block_q, block_kv=block_kv,
            interpret=kernel_interpret(interpret),
        )
    else:
        out = attention_ref(qt, kt, vt, causal=causal)
    return out.swapaxes(1, 2)
