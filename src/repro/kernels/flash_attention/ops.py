"""Jitted public wrapper for the flash-attention Pallas kernel.

Model code keeps (B, S, H, D) layout; the kernel wants (B, H, S, D).
"""
from __future__ import annotations

from functools import partial

import jax

from ..platform import interpret
from .kernel import flash_attention_fwd


@partial(jax.jit, static_argnames=("causal", "window", "scale", "q_block",
                                   "kv_block"))
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    scale: float | None = None, q_block: int = 512,
                    kv_block: int = 512):
    """q: (B, Sq, H, D); k, v: (B, Sk, K, D) — model layout."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_fwd(qt, kt, vt, causal=causal, window=window,
                              scale=scale, q_block=q_block,
                              kv_block=kv_block, interpret=interpret())
    return out.transpose(0, 2, 1, 3)
