"""Jitted public wrapper for the decode-attention Pallas kernel."""
from __future__ import annotations

from functools import partial

import jax

from ..platform import interpret
from .kernel import decode_attention_fwd


@partial(jax.jit, static_argnames=("scale", "kv_block"))
def decode_attention(q, k, v, valid_len, *, scale: float | None = None,
                     kv_block: int = 512):
    """q: (B, H, D) one token per sequence; k/v: (B, S, K, D) cache."""
    return decode_attention_fwd(q, k, v, valid_len, scale=scale,
                                kv_block=kv_block, interpret=interpret())
