"""Mixture-of-Experts layer (DeepSeek-V2 / Llama-4 style).

**Group-local sort-based dispatch** (Switch/GShard grouping): tokens are
split into G groups aligned with the data shards, so the top-k sort,
capacity bucketing, gather and combine-scatter are *local to a shard* —
no data-dependent cross-shard indexing, which XLA SPMD can only lower by
replicating (measured: 295 GiB/device on deepseek-v2 train_4k with a
global sort).  The only cross-shard movement left is along the expert
dimension (buffers (G, E, C, d) sharded (data, model, …)) — the
all-to-all-family traffic a production MoE pays; the §Perf hillclimb
replaces XLA's scatter lowering with an explicit shard_map all-to-all.

Shared experts (DeepSeek-V2 §2.1.2) run densely for every token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..distributed.context import manual_mode, moe_shard_info
from .layers import dense_init, ffn_forward, init_ffn

Params = dict


def init_moe(cfg, key) -> Params:
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "router": dense_init(ks[0], (d, m.n_experts), dt, scale=0.02),
        # routed experts, stacked: (E, d, f) / (E, f, d)
        "experts": {
            "w_gate": dense_init(ks[1], (m.n_experts, d, m.d_ff_expert), dt),
            "w_up": dense_init(jax.random.fold_in(ks[1], 1),
                               (m.n_experts, d, m.d_ff_expert), dt),
            "w_down": dense_init(jax.random.fold_in(ks[1], 2),
                                 (m.n_experts, m.d_ff_expert, d), dt),
        },
    }
    if m.n_shared:
        p["shared"] = init_ffn(cfg, ks[2], d_ff=m.d_ff_expert * m.n_shared)
    return p


def _capacity(cfg, n_tokens: int) -> int:
    m = cfg.moe
    c = int(m.capacity_factor * n_tokens * m.top_k / m.n_experts)
    return max(8, -(-c // 8) * 8)   # round up to 8 for TPU lane alignment


def _group_dispatch(cfg, router_w, xg, cdt):
    """Everything shard-local for one token group.

    xg: (Tg, d).  Returns (buf (E, C, d), slot, src, keep, gate, aux)."""
    m = cfg.moe
    Tg, d = xg.shape
    C = _capacity(cfg, Tg)

    logits = (xg @ router_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # (Tg, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, m.top_k)    # (Tg, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # load-balance auxiliary loss (Switch eq. 4)
    density = jnp.mean(
        jax.nn.one_hot(expert_idx[:, 0], m.n_experts, dtype=jnp.float32), 0)
    density_prob = jnp.mean(probs, axis=0)
    aux = m.aux_loss_coef * m.n_experts * jnp.sum(density * density_prob)

    flat_expert = expert_idx.reshape(-1)                     # (Tg·k,)
    flat_gate = gate_vals.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(Tg), m.top_k)

    order = jnp.argsort(flat_expert)                         # local sort
    sorted_expert = flat_expert[order]
    sorted_token = flat_token[order]
    sorted_gate = flat_gate[order]
    # position within expert segment = rank − first occurrence (memory-
    # lean vs a (Tg·k, E) one-hot cumsum)
    seg_start = jnp.searchsorted(sorted_expert, sorted_expert, side="left")
    pos_in_expert = jnp.arange(sorted_expert.shape[0]) - seg_start
    keep = pos_in_expert < C
    slot = sorted_expert * C + jnp.where(keep, pos_in_expert, 0)
    # dropped entries write zeros at row 0 — `.add` keeps the collision
    # harmless and no pad row is needed (shapes stay divisible)
    src = jnp.where(keep, sorted_token, 0)

    gathered = jnp.where(keep[:, None], xg[src].astype(cdt), 0)
    buf = jnp.zeros((m.n_experts * C, d), cdt).at[slot].add(gathered)
    return (buf.reshape(m.n_experts, C, d), slot, src, keep,
            sorted_gate.astype(cdt), aux)


def _group_combine(ex_out_g, slot, src, keep, gate, Tg, d, cdt):
    """ex_out_g: (E·C, d) for one group → (Tg, d)."""
    contrib = ex_out_g[slot] * gate[:, None]
    contrib = jnp.where(keep[:, None], contrib, 0)
    return jnp.zeros((Tg, d), cdt).at[src].add(contrib)


def _moe_local(cfg, p: Params, x, cdt):
    """Single-shard path (smoke tests, decode with tiny token counts)."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    router_w = p["router"].astype(jnp.float32)
    buf, slot, src, keep, gate, aux = _group_dispatch(cfg, router_w, xt, cdt)
    w = p["experts"]
    gg = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w["w_gate"].astype(cdt)))
    uu = jnp.einsum("ecd,edf->ecf", buf, w["w_up"].astype(cdt))
    ex_out = jnp.einsum("ecf,efd->ecd", gg * uu, w["w_down"].astype(cdt))
    C = ex_out.shape[1]
    out = _group_combine(ex_out.reshape(m.n_experts * C, d),
                         slot, src, keep, gate, T, d, cdt)
    return out.reshape(B, S, d), aux


def _moe_shard_map(cfg, p: Params, x, cdt, mesh, baxes, maxis):
    """Explicit expert-parallel MoE: per-device dispatch + all_to_all.

    Every device owns T/n_dev tokens (the residual layout: batch@data,
    seq@model).  Dispatch/sort/gather are device-local; tokens travel to
    their expert's model-column via ONE all_to_all over the model axis
    (experts replicate across data rows, so no cross-row traffic); the
    combine all_to_all inverts it.  FSDP'd expert weights are explicitly
    all-gathered over the data axis — the same bytes pjit's FSDP moves.
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    B, S, d = x.shape
    E = m.n_experts
    all_axes = (*baxes, maxis)
    w = p["experts"]

    def local(x_blk, router_w, w_gate, w_up, w_down):
        # x_blk: (B_loc, S_loc, d) — the residual block EXACTLY as the
        # (batch@data, seq@model) layout stores it; flattening to tokens
        # happens HERE, locally.  A global (B,S,d)→(T,d) reshape would
        # interleave shards and XLA lowers it by replicating the full
        # activation per layer (§Perf D3: measured 59×8 full-size
        # all-gathers/all-reduces per step).
        with manual_mode():
            xt = x_blk.reshape(-1, x_blk.shape[-1])
            out, aux = _local_body(xt, router_w, w_gate, w_up, w_down)
            return out.reshape(x_blk.shape), aux

    def _local_body(xt, router_w, w_gate, w_up, w_down):
        # xt: (T_loc, d) — this device's tokens.  The FSDP (data-axis)
        # un-shard of this layer's expert weights happens here, inside
        # the loop body where the operand is loop-varying — a pjit-side
        # resharding constraint propagates backward onto the stacked
        # scan xs and un-shards ALL layers at rest (measured +27 GiB).
        if baxes:
            w_gate = jax.lax.all_gather(w_gate, baxes, axis=1, tiled=True)
            w_up = jax.lax.all_gather(w_up, baxes, axis=1, tiled=True)
            w_down = jax.lax.all_gather(w_down, baxes, axis=2, tiled=True)
        buf, slot, src, keep, gate, aux = _group_dispatch(
            cfg, router_w.astype(jnp.float32), xt, cdt)
        C = buf.shape[1]
        # dispatch a2a: (E, C, d) → (E/M, M·C, d) within the model row
        buf = jax.lax.all_to_all(buf, maxis, split_axis=0, concat_axis=1,
                                 tiled=True)
        gg = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(cdt))
        uu = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(cdt))
        eo = jnp.einsum("ecf,efd->ecd", jax.nn.silu(gg) * uu,
                        w_down.astype(cdt))
        # combine a2a: back to (E, C, d) on the owning device
        eo = jax.lax.all_to_all(eo, maxis, split_axis=1, concat_axis=0,
                                tiled=True)
        out = _group_combine(eo.reshape(E * C, d), slot, src, keep, gate,
                             xt.shape[0], d, cdt)
        aux = jax.lax.pmean(aux, all_axes)
        return out, aux

    bspec = baxes if baxes else None
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, maxis, None), P(None, None),
                  P(maxis, bspec, None), P(maxis, bspec, None),
                  P(maxis, None, bspec)),
        out_specs=(P(bspec, maxis, None), P()),
        check_vma=False)
    out, aux = fn(x, p["router"], w["w_gate"], w["w_up"], w["w_down"])
    if "shared" in p:
        # shared experts run densely in pjit land (standard dense FFN)
        out = out + ffn_forward(cfg, p["shared"], x.astype(cdt))
    return out, aux


def moe_forward(cfg, p: Params, x):
    """x: (B, S, d) → (B, S, d), aux_loss."""
    cdt = jnp.dtype(cfg.compute_dtype)
    B, S = x.shape[0], x.shape[1]
    info = moe_shard_info(B * S)
    if info is not None:
        mesh, baxes, maxis = info
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        M = sizes[maxis]
        btot = 1
        for a in baxes:
            btot *= sizes[a]
        if cfg.moe.n_experts % M == 0 and B % btot == 0 and S % M == 0:
            return _moe_shard_map(cfg, p, x, cdt, *info)
    out, aux = _moe_local(cfg, p, x, cdt)
    if "shared" in p:
        B, S, d = x.shape
        xt = x.reshape(B * S, d)
        out = out + ffn_forward(cfg, p["shared"], xt.astype(cdt)
                                ).reshape(B, S, d)
    return out, aux
