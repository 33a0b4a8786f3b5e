"""Pallas TPU RG-LRU scan: blocked gated linear recurrence.

h_t = a_t ⊙ h_{t−1} + x_t, tiled (time-chunk × channel-block).  Grid:
(batch, n_channel_blocks, n_time_chunks) — time is innermost/sequential,
the carry h lives in VMEM scratch between chunks.  Within a chunk the
recurrence over `chunk` steps runs as a fori_loop over 16-row
(bf16-tile-aligned) slices of the VMEM-resident tiles
(the XLA fallback is jax.lax.associative_scan — log-depth but 2× the HBM
traffic of this streaming form).

Channel blocks are lane-aligned (multiples of 128 preferred); VMEM per
program ≈ 2·chunk·db·4B + db·4B — chunk=256, db=512: 1 MiB.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(x_ref, a_ref, h0_ref, o_ref, h_ref, *, chunk: int,
                  rows: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        h_ref[...] = h0_ref[...].astype(jnp.float32)

    row = jax.lax.broadcasted_iota(jnp.int32, (rows, h_ref.shape[1]), 0)

    def step(i, h):
        # `rows` aligned time steps per iteration, read and written
        # through ref slices: Mosaic has no dynamic slice of a value
        r0 = pl.multiple_of(i * rows, rows)
        x = x_ref[pl.ds(r0, rows), :].astype(jnp.float32)   # (rows, db)
        a = a_ref[pl.ds(r0, rows), :].astype(jnp.float32)
        out = jnp.zeros_like(x)
        for r in range(rows):
            h = a[r:r + 1] * h + x[r:r + 1]
            out = jnp.where(row == r, h, out)
        o_ref[pl.ds(r0, rows), :] = out.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk // rows, step, h_ref[...])


def rglru_scan_fwd(x, a, h0, *, chunk: int = 256, channel_block: int = 512,
                   interpret: bool = False):
    """x, a: (B, S, dr); h0: (B, dr) → h sequence (B, S, dr)."""
    B, S, dr = x.shape
    ch = min(chunk, max(S, 8))
    db = min(channel_block, dr)
    pad_s = (-S) % ch
    pad_d = (-dr) % db
    if pad_s or pad_d:
        x = jnp.pad(x, ((0, 0), (0, pad_s), (0, pad_d)))
        # pad gate with ones → padded channels stay zero, padded time
        # steps produce values that are sliced off
        a = jnp.pad(a, ((0, 0), (0, pad_s), (0, pad_d)),
                    constant_values=1.0)
    if pad_d:
        h0 = jnp.pad(h0, ((0, 0), (0, pad_d)))
    n_t = x.shape[1] // ch
    n_d = x.shape[2] // db

    kernel = functools.partial(_rglru_kernel, chunk=ch, rows=math.gcd(ch, 16))
    out = pl.pallas_call(
        kernel,
        grid=(B, n_d, n_t),
        in_specs=[
            pl.BlockSpec((None, ch, db), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((None, ch, db), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((None, 1, db), lambda b, d, t: (b, 0, d)),
        ],
        out_specs=pl.BlockSpec((None, ch, db), lambda b, d, t: (b, t, d)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((1, db), jnp.float32)],
        interpret=interpret,
    )(x, a, h0[:, None, :])
    return out[:, :S, :dr]
