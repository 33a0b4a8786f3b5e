"""Jitted public wrapper for the RG-LRU scan Pallas kernel."""
from __future__ import annotations

from functools import partial

import jax

from ..platform import interpret
from .kernel import rglru_scan_fwd


@partial(jax.jit, static_argnames=("chunk", "channel_block"))
def rglru_scan(x, a, h0, *, chunk: int = 256, channel_block: int = 512):
    """Gated linear recurrence h_t = a_t·h_{t−1} + x_t (B, S, dr)."""
    return rglru_scan_fwd(x, a, h0, chunk=chunk,
                          channel_block=channel_block, interpret=interpret())
