"""Unified decoder: config-driven heterogeneous block stacks.

Every assigned architecture instantiates this skeleton; a
:class:`~repro.configs.base.LayerGroup` describes a *super-block* pattern
(e.g. recurrentgemma's (rglru, rglru, attn_local)) and how many times it
repeats.  Each group is ``jax.lax.scan``-ned over its repeat count — the
compiled HLO contains ONE super-block body per group regardless of depth,
which keeps the 88-layer dry-run cells compilable and is the production
pattern (MaxText scanned layers).  Activation rematerialization wraps the
scan body (``jax.checkpoint``) with a configurable policy.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import LayerGroup, ModelConfig
from ..distributed.context import constrain, decode_tp_active
from . import layers as L
from . import moe as M
from . import recurrent as R
from . import xlstm as X

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# per-block init / forward dispatch
# ---------------------------------------------------------------------------
def _init_mixer(cfg, mixer: str, key) -> Params:
    if mixer in ("attn", "attn_local"):
        return L.init_attn(cfg, key, local=(mixer == "attn_local"))
    if mixer == "mla":
        return L.init_mla(cfg, key)
    if mixer == "rglru":
        return R.init_rglru_block(cfg, key)
    if mixer == "mlstm":
        return X.init_mlstm_block(cfg, key)
    if mixer == "slstm":
        return X.init_slstm_block(cfg, key)
    raise ValueError(mixer)


def _init_ffn(cfg, ffn: str, key) -> Params:
    if ffn == "dense":
        return L.init_ffn(cfg, key)
    if ffn == "moe":
        return M.init_moe(cfg, key)
    return {}


def _mixer_forward(cfg, mixer: str, p, x, positions, cache):
    if mixer == "attn":
        return L.attn_forward(cfg, p, x, positions, cache)
    if mixer == "attn_local":
        return L.attn_forward(cfg, p, x, positions, cache, local=True)
    if mixer == "mla":
        return L.mla_forward(cfg, p, x, positions, cache)
    if mixer == "rglru":
        return R.rglru_forward(cfg, p, x, cache)
    if mixer == "mlstm":
        return X.mlstm_forward(cfg, p, x, cache)
    if mixer == "slstm":
        return X.slstm_forward(cfg, p, x, cache)
    raise ValueError(mixer)


def _block_forward(cfg, mixer: str, ffn: str, p: Params, x, positions, cache):
    """Pre-norm residual block: x + mixer(norm(x)); x + ffn(norm(x))."""
    h, new_cache = _mixer_forward(
        cfg, mixer, p["mixer"], L.rms_norm(x, p["norm1"], cfg.norm_eps),
        positions, cache)
    # branch outputs re-enter the seq-sharded residual layout HERE so the
    # post-projection partial sums lower as reduce-scatters of the
    # (B/dp, S/tp, d) shard instead of full-seq all-reduces (§Perf D4)
    dec = decode_tp_active() and x.shape[1] == 1
    h = constrain(h, "dtp_features" if dec else "residual")
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    if ffn == "dense":
        h = L.ffn_forward(cfg, p["ffn"], L.rms_norm(x, p["norm2"],
                                                    cfg.norm_eps))
        x = x + constrain(h, "dtp_features" if dec else "residual")
    elif ffn == "moe":
        h, aux = M.moe_forward(cfg, p["ffn"], L.rms_norm(x, p["norm2"],
                                                         cfg.norm_eps))
        x = x + constrain(h, "dtp_features" if dec else "residual")
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_layer(cfg: ModelConfig, gi: int, i: int, c: int, k_rest) -> Params:
    """Sub-layer ``i`` of copy ``c`` in group ``gi``, drawn from
    ``fold_in(k_rest, gi·1000 + i·100 + c)``."""
    g = cfg.groups[gi]
    dt = jnp.dtype(cfg.param_dtype)
    key = jax.random.fold_in(k_rest, gi * 1000 + i * 100 + c)
    km, kf = jax.random.split(key)
    p = {
        "norm1": jnp.zeros((cfg.d_model,), dt),
        "mixer": _init_mixer(cfg, g.pattern[i], km),
    }
    if g.ffn_of(i) != "none":      # norm2 only exists with an FFN
        p["norm2"] = jnp.zeros((cfg.d_model,), dt)
    f = _init_ffn(cfg, g.ffn_of(i), kf)
    if f:
        p["ffn"] = f
    return p


@partial(jax.jit, static_argnums=0)
def init_params(cfg: ModelConfig, key) -> Params:
    """Concrete init, jitted with the config static; each group's layers
    are built one at a time into their stacked leaves, so the device
    holds one copy of the weights plus one layer's temporaries.  For
    configs too large to hold use ``param_specs`` (eval_shape)."""
    dt = jnp.dtype(cfg.param_dtype)
    k_embed, k_head, k_rest = jax.random.split(key, 3)
    d = cfg.d_model
    params: Params = {
        "embed": L.dense_init(k_embed, (cfg.vocab_size, d), dt, scale=0.02),
        "final_norm": jnp.zeros((d,), dt),
        "groups": [],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(k_head, (d, cfg.vocab_size), dt)

    for gi, g in enumerate(cfg.groups):
        # a map over the copies builds one layer at a time into the stack
        params["groups"].append({
            f"sub{i}": jax.lax.map(
                lambda c, i=i: init_layer(cfg, gi, i, c, k_rest),
                jnp.arange(g.count))
            for i in range(len(g.pattern))})
    return params


def param_specs(cfg: ModelConfig) -> Params:
    """Shape/dtype skeleton of the params — no allocation (dry-run)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------
def _init_block_cache(cfg, mixer: str, batch: int, max_len: int, dtype):
    if mixer == "attn":
        return L.init_attn_cache(cfg, batch, max_len, dtype)
    if mixer == "attn_local":
        w = min(max_len, cfg.rec.local_window)
        return L.init_attn_cache(cfg, batch, w, dtype)
    if mixer == "mla":
        return L.init_mla_cache(cfg, batch, max_len, dtype)
    if mixer == "rglru":
        return R.init_rglru_state(cfg, batch, dtype)
    if mixer == "mlstm":
        return X.init_mlstm_state(cfg, batch)
    if mixer == "slstm":
        return X.init_slstm_state(cfg, batch)
    raise ValueError(mixer)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> list:
    """Stacked decode caches mirroring the group structure."""
    caches = []
    for g in cfg.groups:
        gc = {}
        for i, mixer in enumerate(g.pattern):
            one = _init_block_cache(cfg, mixer, batch, max_len, dtype)
            gc[f"sub{i}"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (g.count, *x.shape)), one)
        caches.append(gc)
    return caches


def cache_specs(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16) -> list:
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len, dtype))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _run_group(cfg, g: LayerGroup, gp: Params, x, positions, gcache,
               remat_policy: str):
    """Scan one layer group.  gcache: stacked cache dict or None."""

    def body_fn(x, lp, cache):
        new_cache = {} if cache is not None else None
        aux_total = jnp.zeros((), jnp.float32)
        for i, mixer in enumerate(g.pattern):
            c = cache[f"sub{i}"] if cache is not None else None
            x, nc, aux = _block_forward(
                cfg, mixer, g.ffn_of(i), lp[f"sub{i}"], x, positions, c)
            # residual-stream constraint: batch over (pod,data); under a
            # distributed launch the seq dim also shards over model
            # (Megatron-SP) so scanned boundary activations stay bounded.
            # §Perf M2: decode keeps the residual feature-sharded instead
            # (weight-stationary 2D-TP — weights never move)
            if decode_tp_active() and x.shape[1] == 1:
                x = constrain(x, "dtp_features")
            else:
                x = constrain(x, "residual")
            aux_total = aux_total + aux
            if cache is not None:
                new_cache[f"sub{i}"] = nc
        return x, new_cache, aux_total

    if remat_policy != "none":
        policy = {
            "full": None,
            "dots": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        }[remat_policy]
        body_fn = jax.checkpoint(
            body_fn, policy=policy, static_argnums=())

    if gcache is None:
        def scan_body(carry, lp):
            x, aux = carry
            x, _, aux_i = body_fn(x, lp, None)
            return (x, aux + aux_i), None
        (x, aux), _ = jax.lax.scan(scan_body, (x, jnp.zeros((), jnp.float32)), gp)
        return x, None, aux
    else:
        def scan_body(carry, xs):
            x, aux = carry
            lp, cache = xs
            x, nc, aux_i = body_fn(x, lp, cache)
            return (x, aux + aux_i), nc
        (x, aux), new_cache = jax.lax.scan(
            scan_body, (x, jnp.zeros((), jnp.float32)), (gp, gcache))
        return x, new_cache, aux


def forward(cfg: ModelConfig, params: Params, tokens=None, *,
            extra_embeds=None, caches=None, positions=None,
            remat_policy: str = "none", logits_slice: bool = False):
    """Run the decoder.

    tokens: (B, S) int32 ids (may be None for pure-embedding input).
    extra_embeds: (B, P, d) stub-frontend embeddings prepended to the
        token embeddings (vlm patch embeds / audio conditioning).
    caches: from :func:`init_cache` (inference) or None (training).
    positions: explicit positions or None (arange + cache offset).
    logits_slice: return logits for the LAST position only (decode).

    Returns (logits, new_caches, aux_loss).
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    parts = []
    if extra_embeds is not None:
        parts.append(extra_embeds.astype(cdt))
    if tokens is not None:
        parts.append(jnp.take(params["embed"], tokens, axis=0).astype(cdt))
    x = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    # constrain the embedding output immediately: the vocab-sharded
    # lookup otherwise materializes a FULL (B,S,d) activation + its
    # partial-sum all-reduce, and every residual cotangent downstream
    # inherits the unsharded layout (§Perf D3)
    x = constrain(x, "residual")
    B, S, d = x.shape

    if positions is None:
        offset = 0
        if caches is not None:
            offset = _cache_length(caches)
        pos1d = offset + jnp.arange(S)[None, :]
        pos1d = jnp.broadcast_to(pos1d, (B, S))
        if cfg.m_rope_sections:
            positions = jnp.broadcast_to(pos1d[None], (3, B, S))
        else:
            positions = pos1d

    new_caches = [] if caches is not None else None
    aux_total = jnp.zeros((), jnp.float32)
    for gi, g in enumerate(cfg.groups):
        gcache = caches[gi] if caches is not None else None
        x, nc, aux = _run_group(cfg, g, params["groups"][gi], x, positions,
                                gcache, remat_policy)
        aux_total = aux_total + aux
        if caches is not None:
            new_caches.append(nc)

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if logits_slice:
        x = x[:, -1:]
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = (x @ head.astype(cdt)).astype(jnp.float32)
    return logits, new_caches, aux_total


def _cache_length(caches) -> jax.Array:
    """Extract the scalar cache length (any attn/mla sub-cache carries it;
    pure-recurrent stacks track an explicit counter)."""
    for gc in caches:
        for sub in gc.values():
            if isinstance(sub, dict) and "length" in sub:
                ln = sub["length"]
                # stacked over count: all equal — take element 0
                return ln.reshape(-1)[0]
    return jnp.zeros((), jnp.int32)


# ---------------------------------------------------------------------------
# losses / steps (pure functions; jitted by the launchers)
# ---------------------------------------------------------------------------
def loss_fn(cfg: ModelConfig, params: Params, batch: dict,
            remat_policy: str = "full"):
    """Next-token cross entropy (+ MoE aux).  batch: tokens (B,S), labels
    (B,S) with -100 = masked, optional extra_embeds."""
    logits, _, aux = forward(
        cfg, params, batch["tokens"], extra_embeds=batch.get("extra_embeds"),
        remat_policy=remat_policy)
    labels = batch["labels"]
    if "extra_embeds" in batch and batch["extra_embeds"] is not None:
        P = batch["extra_embeds"].shape[1]
        logits = logits[:, P:]
    mask = labels >= 0
    safe = jnp.where(mask, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    nll = jnp.where(mask, nll, 0.0)
    denom = jnp.maximum(mask.sum(), 1)
    return nll.sum() / denom + aux, {
        "loss": nll.sum() / denom, "aux_loss": aux,
        "tokens": mask.sum().astype(jnp.float32)}


def prefill(cfg: ModelConfig, params: Params, tokens, caches, *,
            extra_embeds=None):
    """Prefill: run the prompt through, filling caches; returns last-token
    logits + updated caches."""
    logits, new_caches, _ = forward(
        cfg, params, tokens, extra_embeds=extra_embeds, caches=caches,
        logits_slice=True)
    return logits[:, 0], new_caches


def decode_step(cfg: ModelConfig, params: Params, token, caches):
    """One decode step.  token: (B,) int32 → logits (B, V), new caches."""
    logits, new_caches, _ = forward(
        cfg, params, token[:, None], caches=caches, logits_slice=True)
    return logits[:, 0], new_caches
