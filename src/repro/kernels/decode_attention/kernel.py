"""Pallas TPU decode attention: one query token vs a long KV cache.

The decode bottleneck is HBM bandwidth — the cache is read once per step
and arithmetic intensity is O(1).  Grid: (batch, n_kv_blocks); the
kv-block axis is innermost/sequential, with the (K, G, Dv) accumulator
and (K, G, 1) stats in VMEM scratch, so the kernel streams the cache
through VMEM in (kb, K, D) tiles exactly once — the roofline-optimal
access pattern.  Each tile is turned head-major in VMEM so both matmuls
batch over the kv head.  ``valid_len`` (per batch row, scalar-prefetched
into SMEM) masks the tail; ring-buffer caches (local attention) pass
valid_len=W and rely on entry-order-agnostic masking (post-RoPE keys).

VMEM per program ≈ 2·kb·K·(D+Dv)·2B (double-buffered k, v tiles) +
K·G·Dv·4B; kb=512, K=32, D=128: 8 MiB.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale: float, kv_block: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_k = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32)                      # (K, G, D)
    # heads lead, so both matmuls batch over the kv head
    k = jnp.swapaxes(k_ref[...], 0, 1).astype(jnp.float32)  # (K, kb, D)
    v = jnp.swapaxes(v_ref[...], 0, 1).astype(jnp.float32)  # (K, kb, Dv)

    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale         # (K, G, kb)
    pos = j * kv_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(pos < len_ref[b], s, NEG)

    m_prev = m_ref[...]                                     # (K, G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                 # (K, G, Dv)
    m_ref[...] = m_new

    @pl.when(j == n_k - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


def decode_attention_fwd(q, k, v, valid_len, *, scale: float | None = None,
                         kv_block: int = 512, interpret: bool = False):
    """q: (B, H, D); k, v: (B, S, K, D); valid_len: (B,) int32.

    Returns (B, H, Dv)."""
    B, H, D = q.shape
    _, S, K, Dv = v.shape
    G = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    kb = min(kv_block, max(S, 8))
    pad = (-S) % kb
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_k = k.shape[1] // kb

    kernel = functools.partial(_decode_kernel, scale=scale, kv_block=kb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_k),
        in_specs=[
            pl.BlockSpec((None, K, G, D), lambda b, j, lens: (b, 0, 0, 0)),
            pl.BlockSpec((None, kb, K, D), lambda b, j, lens: (b, j, 0, 0)),
            pl.BlockSpec((None, kb, K, Dv), lambda b, j, lens: (b, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, K, G, Dv),
                               lambda b, j, lens: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((K, G, Dv), jnp.float32),   # acc
            pltpu.VMEM((K, G, 1), jnp.float32),    # running max
            pltpu.VMEM((K, G, 1), jnp.float32),    # denominator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, Dv), q.dtype),
        interpret=interpret,
    )(valid_len.astype(jnp.int32), q.reshape(B, K, G, D), k, v)
    return out.reshape(B, H, Dv)
