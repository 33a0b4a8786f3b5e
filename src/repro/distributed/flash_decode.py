"""Flash-decoding over a sequence-sharded KV cache (shard_map).

§Perf iteration M1 (EXPERIMENTS.md): with the cache sharded
(B@data, S@model, K, hd), a pjit dynamic-update-slice at a traced position
forces XLA's "involuntary full rematerialization" — the whole stacked
cache is copied per layer (measured 2×531 GB/device/step on
mistral-large decode_32k).  The explicit form:

* each model shard owns rows [j·S_loc, (j+1)·S_loc) of the cache and
  updates the write position **locally** (one-row DUS, no replication);
* attention runs as a partial softmax per shard (local max / sum / acc),
  combined with one pmax + two psums of (B, H, ·) — flash-decoding's
  cross-device reduction, bytes ≈ B·H·hd·4 per step (KB-scale, vs the
  GB-scale cache).

The q head dim stays replicated inside a model row (one token of query);
KV heads need no replication handling since all heads are local.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .context import manual_mode

_NEG = jnp.float32(-1e30)


def flash_decode_update(q, k_new, v_new, k_cache, v_cache, length, *,
                        mesh, baxes, maxis, scale: float | None = None):
    """One decode step against an S-sharded cache.

    q: (B, 1, H, hd); k_new/v_new: (B, 1, K, hd);
    k_cache/v_cache: (B, S, K, hd) sharded (batch, model, None, None);
    length: scalar int32 — current cache fill (the write position).

    Returns (out (B, 1, H, hd), new_k_cache, new_v_cache)."""
    B, _, H, hd = q.shape
    S = k_cache.shape[1]
    K = k_cache.shape[2]
    G = H // K
    M = int(dict(zip(mesh.axis_names, mesh.devices.shape))[maxis])
    S_loc = S // M
    scale_ = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])

    def local(q_l, kn, vn, kc, vc, length):
        with manual_mode():
            j = jax.lax.axis_index(maxis)
            slot = length - j * S_loc
            in_range = jnp.logical_and(slot >= 0, slot < S_loc)
            slot_c = jnp.clip(slot, 0, S_loc - 1)
            # one-row local update: read the row, blend, write back
            row_k = jax.lax.dynamic_slice(
                kc, (0, slot_c, 0, 0), (kc.shape[0], 1, K, hd))
            row_v = jax.lax.dynamic_slice(
                vc, (0, slot_c, 0, 0), (vc.shape[0], 1, K, hd))
            blend_k = jnp.where(in_range, kn.astype(kc.dtype), row_k)
            blend_v = jnp.where(in_range, vn.astype(vc.dtype), row_v)
            kc = jax.lax.dynamic_update_slice(kc, blend_k, (0, slot_c, 0, 0))
            vc = jax.lax.dynamic_update_slice(vc, blend_v, (0, slot_c, 0, 0))

            # partial softmax over the local rows — the cache is consumed
            # in ITS OWN dtype with an f32 accumulator (MXU-native
            # bf16×bf16→f32); an .astype(f32) on kc would materialize an
            # f32 copy of the cache and poison the carried dtype (XLA
            # then converts the whole stacked cache per layer)
            qh = q_l[:, 0].reshape(q_l.shape[0], K, G, hd)
            s = jnp.einsum("bkgd,bskd->bkgs", qh, kc,
                           preferred_element_type=jnp.float32) * scale_
            kpos = j * S_loc + jnp.arange(S_loc)
            s = s + _NEG * (kpos > length)[None, None, None]
            m_loc = s.max(axis=-1)
            p = jnp.exp(s - m_loc[..., None])
            l_loc = p.sum(axis=-1)
            acc = jnp.einsum("bkgs,bskd->bkgd", p.astype(vc.dtype), vc,
                             preferred_element_type=jnp.float32)

            # flash-decoding combine across the model axis
            m_g = jax.lax.pmax(m_loc, maxis)
            corr = jnp.exp(m_loc - m_g)
            l_g = jax.lax.psum(l_loc * corr, maxis)
            acc_g = jax.lax.psum(acc * corr[..., None], maxis)
            out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
            out = out.reshape(q_l.shape[0], 1, H, hd).astype(q_l.dtype)
            return out, kc, vc

    bspec = baxes if baxes else None
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, None, None, None), P(bspec, None, None, None),
                  P(bspec, None, None, None),
                  P(bspec, maxis, None, None), P(bspec, maxis, None, None),
                  P()),
        out_specs=(P(bspec, None, None, None),
                   P(bspec, maxis, None, None), P(bspec, maxis, None, None)),
        check_vma=False)
    return fn(q, k_new, v_new, k_cache, v_cache,
              jnp.asarray(length, jnp.int32))
