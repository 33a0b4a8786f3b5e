"""Per-architecture smoke tests (reduced configs, assignment requirement):
one forward/train step on CPU asserting shapes + no NaNs, plus a decode
step — same code paths as the full configs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs, reduced
from repro.models import (decode_step, forward, init_cache, init_params,
                          loss_fn, prefill)
from repro.models.frontends import make_patch_embeds
from repro.models.layers import dense_init
from repro.models.transformer import init_layer

ARCHS = list_archs()


@pytest.fixture(scope="module")
def rigs():
    out = {}
    key = jax.random.PRNGKey(0)
    for arch in ARCHS:
        cfg = reduced(get_config(arch))
        out[arch] = (cfg, init_params(cfg, key))
    return out


def _batch(cfg, B=2, S=16):
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.frontend == "vision_stub":
        batch["extra_embeds"] = make_patch_embeds(
            key, B, cfg.n_visual_tokens, cfg.d_model)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finiteness(rigs, arch):
    cfg, params = rigs[arch]
    batch = _batch(cfg)
    logits, _, aux = forward(cfg, params, batch["tokens"],
                             extra_embeds=batch.get("extra_embeds"))
    S = batch["tokens"].shape[1] + (
        cfg.n_visual_tokens if cfg.frontend == "vision_stub" else 0)
    assert logits.shape == (2, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    assert np.isfinite(float(aux))


def _eager_stack_init(cfg, key):
    """Every layer built eagerly, then each stack ``jnp.stack``-ed."""
    dt = jnp.dtype(cfg.param_dtype)
    k_embed, k_head, k_rest = jax.random.split(key, 3)
    d = cfg.d_model
    want = {"embed": dense_init(k_embed, (cfg.vocab_size, d), dt, scale=0.02),
            "final_norm": jnp.zeros((d,), dt), "groups": []}
    if not cfg.tie_embeddings:
        want["lm_head"] = dense_init(k_head, (d, cfg.vocab_size), dt)
    for gi, g in enumerate(cfg.groups):
        want["groups"].append({
            f"sub{i}": jax.tree.map(lambda *xs: jnp.stack(xs), *[
                init_layer(cfg, gi, i, c, k_rest) for c in range(g.count)])
            for i in range(len(g.pattern))})
    return want


@pytest.mark.parametrize("arch", ARCHS)
def test_init_params_equals_eager_stack(rigs, arch):
    """The jitted init, which maps over each stack's layers, draws the
    bits of the eager layer-by-layer init and stack."""
    cfg, params = rigs[arch]
    want = _eager_stack_init(cfg, jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(want)
    for got, ref in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert got.dtype == ref.dtype and got.shape == ref.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_init_params_bf16_equals_eager_stack():
    cfg = dataclasses.replace(reduced(get_config("phi3-mini-3.8b")),
                              param_dtype="bfloat16")
    key = jax.random.PRNGKey(5)
    got = jax.tree.leaves(init_params(cfg, key))
    want = jax.tree.leaves(_eager_stack_init(cfg, key))
    assert {x.dtype for x in got} == {jnp.dtype(jnp.bfloat16)}
    assert all(np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
               for a, b in zip(got, want, strict=True))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_loss_finite(rigs, arch):
    cfg, params = rigs[arch]
    loss, metrics = loss_fn(cfg, params, _batch(cfg), remat_policy="none")
    assert np.isfinite(float(loss))
    # random tokens ⇒ loss ≈ ln(V); sanity band
    assert 0.5 * np.log(cfg.vocab_size) < float(loss) < 3 * np.log(
        cfg.vocab_size)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step(rigs, arch):
    cfg, params = rigs[arch]
    B = 2
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, 8), 0,
                                cfg.vocab_size)
    caches = init_cache(cfg, B, 24)
    logits, caches = prefill(cfg, params, tokens, caches)
    assert logits.shape == (B, cfg.vocab_size)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    logits2, caches = decode_step(cfg, params, nxt, caches)
    assert logits2.shape == (B, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits2)).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_param_count_close_to_published(rigs, arch):
    """Full-config analytic param count lands near the advertised size."""
    published = {
        "mistral-large-123b": 123e9, "deepseek-coder-33b": 33e9,
        "minicpm-2b": 2.7e9, "phi3-mini-3.8b": 3.8e9,
        "deepseek-v2-236b": 236e9, "llama4-maverick-400b-a17b": 400e9,
        "musicgen-large": 3.3e9, "recurrentgemma-2b": 2.7e9,
        "xlstm-1.3b": 1.3e9, "qwen2-vl-7b": 7.6e9,
    }
    n = get_config(arch).param_count()
    # within 2x of the nameplate (block-structure details vary)
    assert published[arch] / 2 < n < published[arch] * 2.1, n


@pytest.mark.slow
def test_grad_flows_through_every_param():
    """No dead parameters: every leaf receives a nonzero gradient
    somewhere in a mixed-family config."""
    for arch in ("recurrentgemma-2b", "xlstm-1.3b", "deepseek-v2-236b"):
        cfg = reduced(get_config(arch))
        params = init_params(cfg, jax.random.PRNGKey(0))
        batch = _batch(cfg, B=2, S=8)
        grads = jax.grad(
            lambda p: loss_fn(cfg, p, batch, remat_policy="none")[0])(params)
        flat = jax.tree_util.tree_flatten_with_path(grads)[0]
        dead = [jax.tree_util.keystr(path) for path, g in flat
                if float(jnp.abs(g).max()) == 0.0]
        # routers/expert subsets may legitimately see no tokens in a tiny
        # batch; everything else must be live
        dead = [d for d in dead if "expert" not in d and "router" not in d]
        assert not dead, f"{arch}: dead grads at {dead[:5]}"
