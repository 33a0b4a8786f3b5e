"""Shared model layers: norms, RoPE / M-RoPE, GQA / MLA attention, SwiGLU.

All attention paths use a **chunked online-softmax** formulation (the pure
JAX stand-in for the Pallas flash-attention kernel in ``repro.kernels``):
memory stays O(block²) instead of O(S²), so the 32k-prefill dry-run cells
compile with bounded temporaries — matching what the TPU kernel does in
VMEM (DESIGN.md §8).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.context import constrain, decode_tp_active

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# initializers / norms
# ---------------------------------------------------------------------------
def dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    s = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    # the barrier keeps XLA from folding the scale into the sampler when
    # init runs under jit, so jitted init draws the eager init's bits
    z = jax.lax.optimization_barrier(jax.random.normal(key, shape))
    return (z * s).astype(dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------------
# RoPE (+ M-RoPE, Qwen2-VL §2.1)
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               m_rope_sections: tuple[int, ...] = ()) -> jax.Array:
    """Rotate ``x`` (..., S, H, D) by positions.

    ``positions``: (B, S) for standard RoPE, or (3, B, S) for M-RoPE where
    the head-dim pair spectrum is partitioned into (t, h, w) sections
    (Qwen2-VL).  For text tokens the three coordinates coincide and M-RoPE
    reduces to 1-D RoPE, which is how the text-backbone dry-run drives it.
    """
    D = x.shape[-1]
    freqs = rope_freqs(D, theta)                      # (D/2,)
    if m_rope_sections:
        assert positions.ndim == 3, "M-RoPE needs (3, B, S) positions"
        sec = np.asarray(m_rope_sections)
        assert sec.sum() == D // 2, (sec, D)
        # choose which coordinate (t/h/w) drives each frequency pair
        coord_of_pair = np.repeat(np.arange(len(sec)), sec)   # (D/2,)
        pos = positions[coord_of_pair, ...]                   # (D/2, B, S)
        angles = jnp.einsum("dbs,d->bsd", pos.astype(jnp.float32), freqs)
    else:
        if positions.ndim == 3:   # degenerate M-RoPE positions on 1-D path
            positions = positions[0]
        angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,D/2)
    cos = jnp.cos(angles)[..., None, :]               # (B, S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# chunked online-softmax attention (flash-attention semantics in pure JAX)
# ---------------------------------------------------------------------------
_NEG = jnp.float32(-1e30)
# Masking is ADDITIVE (0 / −1e30 f32 bias), never boolean `where`: select
# ops materialize broadcast pred tensors that XLA hoists out of the layer
# scan as multi-GiB loop invariants, and their backward saves the mask.
# exp(s − m) of a −1e30 entry underflows to exactly 0 once any real entry
# sets m, and the online rescale (alpha) wipes any early fully-masked
# garbage.


def _block_bias(qpos, kpos, Sk, causal, window):
    bias = _NEG * (kpos[None, :] >= Sk)                   # kv padding
    if causal:
        bias = bias + _NEG * (qpos[:, None] < kpos[None, :])
    if window is not None:
        bias = bias + _NEG * (qpos[:, None] - kpos[None, :] >= window)
    return bias                                           # (qb, kb) f32


def _chunk_shapes(q, k, v, q_block, kv_block):
    B, Sq, H, D = q.shape
    _, Sk, K, Dv = v.shape
    G = H // K
    qb, kb = min(q_block, Sq), min(kv_block, Sk)
    n_q, n_k = -(-Sq // qb), -(-Sk // kb)
    pad_q, pad_k = n_q * qb - Sq, n_k * kb - Sk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    # block tensors keep (batch → data, kv-heads → model); without the
    # constraint XLA gathered the FULL (B, H) q per layer on the MLA
    # cells (§Perf D1: 3.8 TB/step of all-gathers on deepseek-v2)
    qc = constrain(q.reshape(B, n_q, qb, K, G, D), "flash_blocks")
    kc = constrain(k.reshape(B, n_k, kb, K, D), "flash_blocks")
    vc = constrain(v.reshape(B, n_k, kb, K, Dv), "flash_blocks")
    return qc, kc, vc, (B, Sq, Sk, H, K, G, D, Dv, qb, kb, n_q, n_k)


def _chunk_scan_attn(q, k, v, *, causal: bool, q_offset, window: int | None,
                     q_block: int, kv_block: int, scale: float,
                     with_lse: bool = False):
    """Online-softmax chunked attention (flash semantics, O(block²) temp).

    q: (B, Sq, H, D) with H a multiple of K; k/v: (B, Sk, K, D).
    Returns (B, Sq, H, Dv) [+ logsumexp (B, K, G, n_q·qb) if with_lse]."""
    qc, kc, vc, dims = _chunk_shapes(q, k, v, q_block, kv_block)
    B, Sq, Sk, H, K, G, D, Dv, qb, kb, n_q, n_k = dims
    q_pos = q_offset + jnp.arange(n_q * qb).reshape(n_q, qb)
    k_pos = jnp.arange(n_k * kb).reshape(n_k, kb)

    def per_qblock(qblk, qpos):
        def body(carry, inputs):
            acc, m, l = carry
            kblk, vblk, kpos = inputs
            s = jnp.einsum("bqkgd,bskd->bkgqs", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            s = s + _block_bias(qpos, kpos, Sk, causal, window)[
                None, None, None]
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, vblk,
                preferred_element_type=jnp.float32)
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((B, K, G, qb, Dv), jnp.float32)
        m0 = jnp.full((B, K, G, qb), _NEG, jnp.float32)
        l0 = jnp.zeros((B, K, G, qb), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            body, (acc0, m0, l0),
            (kc.swapaxes(0, 1), vc.swapaxes(0, 1), k_pos))
        l_safe = jnp.maximum(l, 1e-30)
        out = acc / l_safe[..., None]
        return out, m + jnp.log(l_safe)               # (B,K,G,qb,[Dv])

    outs, lse = jax.lax.map(
        lambda args: per_qblock(*args),
        (qc.swapaxes(0, 1), q_pos))                   # (nq,B,K,G,qb,…)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, n_q * qb, H, Dv)
    out = out[:, :Sq]
    if with_lse:
        return out, lse.transpose(1, 2, 3, 0, 4).reshape(B, K, G, n_q * qb)
    return out


# ---------------------------------------------------------------------------
# flash attention with custom VJP (training path)
#
# lax.scan's default VJP saves per-iteration residuals — i.e. the FULL
# S×S softmax matrix across all (q-block, kv-block) pairs, ~48 GiB/device
# at the 4k-train cells.  The flash backward recomputes p blockwise from
# the saved logsumexp instead: residuals are q, k, v, out, lse — linear
# in S.  This is exactly the algorithm the Pallas kernel implements on
# TPU (kernels/flash_attention).
# ---------------------------------------------------------------------------
def _make_flash(causal: bool, window: int | None, q_block: int,
                kv_block: int, scale: float):

    @jax.custom_vjp
    def flash(q, k, v):
        return _chunk_scan_attn(q, k, v, causal=causal, q_offset=0,
                                window=window, q_block=q_block,
                                kv_block=kv_block, scale=scale)

    def fwd(q, k, v):
        out, lse = _chunk_scan_attn(q, k, v, causal=causal, q_offset=0,
                                    window=window, q_block=q_block,
                                    kv_block=kv_block, scale=scale,
                                    with_lse=True)
        return out, (q, k, v, out, lse)

    def bwd(res, dout):
        q, k, v, out, lse = res
        in_dtypes = (q.dtype, k.dtype, v.dtype)
        qc, kc, vc, dims = _chunk_shapes(q, k, v, q_block, kv_block)
        B, Sq, Sk, H, K, G, D, Dv, qb, kb, n_q, n_k = dims
        pad_q = n_q * qb - Sq
        dout = jnp.pad(dout.astype(jnp.float32),
                       ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        out_p = jnp.pad(out.astype(jnp.float32),
                        ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        doc = constrain(dout.reshape(B, n_q, qb, K, G, Dv), "flash_blocks")
        ouc = constrain(out_p.reshape(B, n_q, qb, K, G, Dv), "flash_blocks")
        lse_p = jnp.pad(lse, ((0, 0), (0, 0), (0, 0), (0, 0))) \
            .reshape(B, K, G, n_q, qb)
        q_pos = jnp.arange(n_q * qb).reshape(n_q, qb)
        k_pos = jnp.arange(n_k * kb).reshape(n_k, kb)
        # D_i = rowsum(dout ⊙ out)
        Drow = jnp.einsum("bnqkgd,bnqkgd->bkgnq", doc, ouc)

        def per_qblock(args):
            qblk, do_blk, qpos, lse_blk, D_blk = args

            def body(dq_acc, inputs):
                kblk, vblk, kpos = inputs
                s = jnp.einsum("bqkgd,bskd->bkgqs", qblk, kblk,
                               preferred_element_type=jnp.float32) * scale
                s = s + _block_bias(qpos, kpos, Sk, causal, window)[
                    None, None, None]
                p = jnp.exp(s - lse_blk[..., None])        # (B,K,G,qb,kb)
                dv = jnp.einsum("bkgqs,bqkgd->bskd", p, do_blk)
                dp = jnp.einsum("bqkgd,bskd->bkgqs", do_blk, vblk)
                ds = p * (dp - D_blk[..., None]) * scale
                dq_acc = dq_acc + jnp.einsum("bkgqs,bskd->bqkgd", ds, kblk)
                dk = jnp.einsum("bkgqs,bqkgd->bskd", ds, qblk)
                return dq_acc, (dk, dv)

            dq0 = jnp.zeros((B, qb, K, G, D), jnp.float32)
            dq, (dks, dvs) = jax.lax.scan(
                body, dq0, (kc.swapaxes(0, 1).astype(jnp.float32),
                            vc.swapaxes(0, 1).astype(jnp.float32), k_pos))
            return dq, dks, dvs                     # dks: (n_k,B,kb,K,D)

        dqs, dks, dvs = jax.lax.map(per_qblock, (
            qc.swapaxes(0, 1).astype(jnp.float32),
            doc.swapaxes(0, 1),
            q_pos,
            lse_p.transpose(3, 0, 1, 2, 4),
            Drow.transpose(3, 0, 1, 2, 4)))
        dq = dqs.transpose(1, 0, 2, 3, 4, 5).reshape(
            B, n_q * qb, H, D)[:, :Sq]
        dk = dks.sum(0).transpose(1, 0, 2, 3, 4).reshape(
            B, n_k * kb, K, D)[:, :Sk]
        dv = dvs.sum(0).transpose(1, 0, 2, 3, 4).reshape(
            B, n_k * kb, K, Dv)[:, :Sk]
        return (dq.astype(in_dtypes[0]), dk.astype(in_dtypes[1]),
                dv.astype(in_dtypes[2]))

    flash.defvjp(fwd, bwd)
    return flash


def attention(q, k, v, *, causal: bool = True, q_offset=0,
              window: int | None = None, q_block: int = 1024,
              kv_block: int = 1024, scale: float | None = None,
              valid_len=None):
    """Grouped-query attention with flash semantics.

    q: (B, Sq, H, D); k, v: (B, Sk, K, D); H % K == 0.
    ``q_offset`` is the absolute position of q[0] (decode: cache length).
    ``window``: sliding-window size (recurrentgemma local attention).
    ``valid_len``: if given (ring caches), mask is position-agnostic —
    entries with index ≥ valid_len are invalid, everything else attends.
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if q.shape[1] == 1:
        # decode fast path: no chunking needed, one token of query
        B, _, H, D = q.shape
        K = k.shape[2]
        G = H // K
        qh = q.reshape(B, K, G, D)
        s = jnp.einsum("bkgd,bskd->bkgs", qh.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        kpos = jnp.arange(k.shape[1])
        if valid_len is not None:
            mask = kpos < valid_len
        else:
            mask = kpos <= q_offset
            if window is not None:
                mask = mask & (q_offset - kpos < window)
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
        return out.reshape(B, 1, H, v.shape[-1]).astype(q.dtype)
    if isinstance(q_offset, int) and q_offset == 0:
        # training / fresh-prefill path: flash custom-VJP (blockwise-
        # recomputing backward — O(S) residuals instead of O(S²))
        flash = _make_flash(causal, window, q_block, kv_block, scale)
        return flash(q, k, v).astype(q.dtype)
    out = _chunk_scan_attn(q, k, v, causal=causal, q_offset=q_offset,
                           window=window, q_block=q_block, kv_block=kv_block,
                           scale=scale)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer (mistral / deepseek-coder / minicpm / phi3 / musicgen /
# qwen2-vl / recurrentgemma-local)
# ---------------------------------------------------------------------------
def init_attn(cfg, key, local: bool = False) -> Params:
    d, hd = cfg.d_model, cfg.head_dim_
    H, K = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "wq": dense_init(ks[0], (d, H * hd), dt),
        "wk": dense_init(ks[1], (d, K * hd), dt),
        "wv": dense_init(ks[2], (d, K * hd), dt),
        "wo": dense_init(ks[3], (H * hd, d), dt),
    }


def attn_forward(cfg, p: Params, x, positions, cache=None, *,
                 local: bool = False, layer_slot: int = 0):
    """x: (B, S, d).  cache: dict(k, v, length) for decode, or None.

    Returns (out, new_cache).  KV cache layout: (B, S_max, K, hd).
    """
    B, S, d = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    cdt = jnp.dtype(cfg.compute_dtype)
    dtp = decode_tp_active() and S == 1
    if dtp:
        # §Perf M2: project with d contracted over the data axis (weights
        # stay put; psum partials), then bring q/k/v to batch-sharded
        # full-head layout for the cache/flash-decode (KB-scale a2a)
        x = constrain(x, "dtp_features")
        q = constrain((x @ p["wq"].astype(cdt)).reshape(B, S, H, hd),
                      "batch_only")
        k = constrain((x @ p["wk"].astype(cdt)).reshape(B, S, K, hd),
                      "batch_only")
        v = constrain((x @ p["wv"].astype(cdt)).reshape(B, S, K, hd),
                      "batch_only")
    else:
        # SP→TP transition: projections emit head-sharded tensors (seq
        # all-gathers here, once per block, instead of weight gathers)
        q = constrain((x @ p["wq"].astype(cdt)).reshape(B, S, H, hd), "heads")
        k = constrain((x @ p["wk"].astype(cdt)).reshape(B, S, K, hd), "heads")
        v = constrain((x @ p["wv"].astype(cdt)).reshape(B, S, K, hd), "heads")
    q = apply_rope(q, positions, cfg.rope_theta, cfg.m_rope_sections)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.m_rope_sections)
    window = cfg.rec.local_window if local else None
    if cache is not None:
        length = cache["length"]                       # scalar int32
        W = cache["k"].shape[1]
        if local and W <= window:
            # ---- ring-buffer cache: holds only the last W tokens ----
            # keys are cached *post-RoPE* so relative rotation survives
            # the wrap-around; masking is pure validity (no causality
            # needed — the ring holds exactly the past window).
            if S == 1:
                slot = jax.lax.rem(length, W)
                k_cache = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
                v_cache = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
                out = attention(q, k_cache.astype(cdt), v_cache.astype(cdt),
                                valid_len=jnp.minimum(length + 1, W))
            else:
                # fresh prefill into a ring (length assumed 0): attend with
                # the windowed chunked path, then scatter the last W tokens
                # at their ring slots (static index permutation).
                out = attention(q, k, v, causal=True, window=window)
                tail = min(S, W)
                ring_idx = np.arange(S - tail, S) % W
                k_cache = cache["k"].at[:, ring_idx].set(
                    k[:, S - tail:].astype(cache["k"].dtype))
                v_cache = cache["v"].at[:, ring_idx].set(
                    v[:, S - tail:].astype(cache["v"].dtype))
            new_cache = {"k": k_cache, "v": v_cache, "length": length + S}
        else:
            from ..distributed.context import decode_shard_info
            info = decode_shard_info(B, cache["k"].shape[1]) \
                if S == 1 and not local else None
            if info is not None:
                # §Perf M1: shard_map flash-decode — local one-row cache
                # update + partial-softmax combine (KB-scale collectives)
                # instead of pjit DUS on a sharded dim (which replicates
                # the whole stacked cache per layer)
                from ..distributed.flash_decode import flash_decode_update
                mesh, baxes, maxis = info
                out, k_cache, v_cache = flash_decode_update(
                    q, k, v, cache["k"], cache["v"], length,
                    mesh=mesh, baxes=baxes, maxis=maxis)
                new_cache = {"k": k_cache, "v": v_cache,
                             "length": length + S}
            else:
                k_cache = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, length, 0, 0))
                v_cache = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, length, 0, 0))
                out = attention(q, k_cache.astype(cdt), v_cache.astype(cdt),
                                q_offset=length, window=window)
                new_cache = {"k": k_cache, "v": v_cache, "length": length + S}
    else:
        out = attention(q, k, v, causal=True, window=window)
        new_cache = None
    # contract H·hd over the model axis — wo stays put; without this the
    # attention output loses its batch sharding and the post-wo partial
    # all-reduce runs on the FULL (B,S,d) tensor (§Perf D2)
    out = constrain(out.reshape(B, S, H, hd), "heads")
    out = out.reshape(B, S, H * hd) @ p["wo"].astype(cdt)
    if dtp:
        out = constrain(out, "dtp_features")
    return out, new_cache


def init_attn_cache(cfg, batch: int, max_len: int, dtype) -> Params:
    K, hd = cfg.n_kv_heads, cfg.head_dim_
    return {
        "k": jnp.zeros((batch, max_len, K, hd), dtype),
        "v": jnp.zeros((batch, max_len, K, hd), dtype),
        "length": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek-V2 §2.1)
# ---------------------------------------------------------------------------
def init_mla(cfg, key) -> Params:
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qd = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 7)
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "w_dkv": dense_init(ks[0], (d, m.kv_lora_rank), dt),
        "w_krope": dense_init(ks[1], (d, m.qk_rope_dim), dt),
        "w_uk": dense_init(ks[2], (m.kv_lora_rank, H * m.qk_nope_dim), dt),
        "w_uv": dense_init(ks[3], (m.kv_lora_rank, H * m.v_head_dim), dt),
        "wo": dense_init(ks[4], (H * m.v_head_dim, d), dt),
    }
    if m.q_lora_rank:
        p["w_dq"] = dense_init(ks[5], (d, m.q_lora_rank), dt)
        p["w_uq"] = dense_init(ks[6], (m.q_lora_rank, H * qd), dt)
    else:
        p["wq"] = dense_init(ks[5], (d, H * qd), dt)
    return p


def mla_forward(cfg, p: Params, x, positions, cache=None):
    """Latent-KV attention.  Cache stores (c_kv, k_rope) — the MLA memory
    saving: rank+rope_dim per token instead of 2·K·hd."""
    m = cfg.mla
    B, S, d = x.shape
    H = cfg.n_heads
    cdt = jnp.dtype(cfg.compute_dtype)
    if m.q_lora_rank:
        q = (x @ p["w_dq"].astype(cdt)) @ p["w_uq"].astype(cdt)
    else:
        q = x @ p["wq"].astype(cdt)
    q = q.reshape(B, S, H, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    c_kv = x @ p["w_dkv"].astype(cdt)                       # (B,S,rank)
    k_rope = apply_rope((x @ p["w_krope"].astype(cdt))[:, :, None, :],
                        positions, cfg.rope_theta)[:, :, 0]  # (B,S,rope)

    if cache is not None:
        length = cache["length"]
        c_kv_c = jax.lax.dynamic_update_slice(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, length, 0))
        k_rope_c = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, length, 0))
        new_cache = {"c_kv": c_kv_c, "k_rope": k_rope_c, "length": length + S}
        c_all, kr_all, q_off = c_kv_c.astype(cdt), k_rope_c.astype(cdt), length
    else:
        new_cache = None
        c_all, kr_all, q_off = c_kv, k_rope, 0

    k_nope = constrain((c_all @ p["w_uk"].astype(cdt)).reshape(
        B, -1, H, m.qk_nope_dim), "heads")
    v = constrain((c_all @ p["w_uv"].astype(cdt)).reshape(
        B, -1, H, m.v_head_dim), "heads")
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_all[:, :, None, :],
                                  (*kr_all.shape[:2], H, m.qk_rope_dim))],
        axis=-1)
    k = constrain(k, "heads")
    q_full = constrain(jnp.concatenate([q_nope, q_rope], axis=-1), "heads")
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    out = attention(q_full, k, v, causal=True, q_offset=q_off, scale=scale)
    out = constrain(out, "heads")                  # §Perf D2 (see attn)
    out = out.reshape(B, S, H * m.v_head_dim) @ p["wo"].astype(cdt)
    return out, new_cache


def init_mla_cache(cfg, batch: int, max_len: int, dtype) -> Params:
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, m.qk_rope_dim), dtype),
        "length": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# SwiGLU FFN
# ---------------------------------------------------------------------------
def init_ffn(cfg, key, d_ff: int | None = None) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "w_gate": dense_init(ks[0], (d, f), dt),
        "w_up": dense_init(ks[1], (d, f), dt),
        "w_down": dense_init(ks[2], (f, d), dt),
    }


def ffn_forward(cfg, p: Params, x):
    cdt = jnp.dtype(cfg.compute_dtype)
    if decode_tp_active() and x.shape[-2] == 1:
        # §Perf M2 — weight-stationary 2D-TP decode: contract d over the
        # data axis and f over the model axis so the 2D-sharded weights
        # never move; the collectives are psums of (B, 1, f/16) partials
        x = constrain(x, "dtp_features")
        g = jax.nn.silu(constrain(x @ p["w_gate"].astype(cdt), "dtp_hidden"))
        u = constrain(x @ p["w_up"].astype(cdt), "dtp_hidden")
        out = (g * u) @ p["w_down"].astype(cdt)
        return constrain(out, "dtp_features")
    g = jax.nn.silu(constrain(x @ p["w_gate"].astype(cdt), "ffn_hidden"))
    u = constrain(x @ p["w_up"].astype(cdt), "ffn_hidden")
    return (g * u) @ p["w_down"].astype(cdt)
