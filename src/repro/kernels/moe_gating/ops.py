"""Jitted public wrapper for the fused MoE gating Pallas kernel."""
from __future__ import annotations

from functools import partial

import jax

from ..platform import interpret
from .kernel import moe_gating_fwd


@partial(jax.jit, static_argnames=("top_k", "capacity", "token_block"))
def moe_gating(logits, *, top_k: int, capacity: int, token_block: int = 256):
    """Fused router: softmax → top-k → FCFS capacity slots.  (T, E) in."""
    return moe_gating_fwd(logits, top_k=top_k, capacity=capacity,
                          token_block=token_block, interpret=interpret())
