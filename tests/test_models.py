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
from repro.models.transformer import (init_layer, insert_row, rows_cache,
                                      takes_rows)

ARCHS = list_archs()
ROWS_ARCHS = [a for a in ARCHS if takes_rows(get_config(a))]


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


# ----------------------------------------------------------------------
# ragged decode: one step over a cache whose rows sit at their own lengths
# ----------------------------------------------------------------------
def _set_length(caches, row, n):
    return [{k: {**sub, "length": sub["length"].at[:, row].set(n)}
             for k, sub in gc.items()} for gc in caches]


@pytest.mark.parametrize("arch", ROWS_ARCHS)
def test_rows_decode_matches_each_rows_own_decode(rigs, arch):
    """Each row of one decode step over a :func:`rows_cache` gives the
    logits and K/V of that row's own batch-1 decode: rows at different
    lengths, one at ``max_seq - 1``, and an idle row held past the end
    (finite, and writing only inside its own row)."""
    cfg, params = rigs[arch]
    T, idle = 16, 2
    lens = {0: 5, 1: 9, 3: T - 1}
    caches = rows_cache(cfg, 4, T)
    rng = np.random.default_rng(0)
    toks, own = np.zeros(4, np.int32), {}
    for b, n in lens.items():
        prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, n)), jnp.int32)
        logits, own[b] = prefill(cfg, params, prompt, init_cache(cfg, 1, T))
        caches = insert_row(caches, own[b], b)
        toks[b] = int(jnp.argmax(logits[0]))
    caches = _set_length(caches, idle, T + 3)
    before = caches[0]["sub0"]["k"][:, idle]

    logits, new = decode_step(cfg, params, jnp.asarray(toks), caches)
    assert logits.shape == (4, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    sub = new[0]["sub0"]
    # a batch of rows may round differently from one row in bf16 compute
    tol = 2e-5 if cfg.compute_dtype == "float32" else 3e-2
    for b, c in own.items():
        want, c1 = decode_step(cfg, params, jnp.asarray(toks[b:b + 1]), c)
        np.testing.assert_allclose(logits[b], want[0], rtol=tol, atol=tol)
        for name in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(sub[name][:, b], np.float32),
                np.asarray(c1[0]["sub0"][name][:, 0], np.float32),
                rtol=tol, atol=tol)
    np.testing.assert_array_equal(
        sub["length"][0], [lens[0] + 1, lens[1] + 1, T + 4, lens[3] + 1])
    np.testing.assert_array_equal(sub["k"][:, idle, :T - 1],
                                  before[:, :T - 1])


@pytest.mark.parametrize("arch", ["recurrentgemma-2b", "deepseek-v2-236b"])
def test_rows_cache_refused_where_a_layer_keeps_one_position(rigs, arch):
    """Ring windows and latent caches keep their scalar length: the
    config cannot build a per-row cache, and a per-row length handed to
    the layer raises rather than being broadcast."""
    cfg, params = rigs[arch]
    assert not takes_rows(cfg)
    with pytest.raises(ValueError):
        rows_cache(cfg, 2, 16)
    caches = init_cache(cfg, 2, 16)
    ragged = [{k: ({**sub, "length": jnp.zeros((*sub["length"].shape, 2),
                                               jnp.int32)}
                   if "length" in sub else sub)
               for k, sub in gc.items()} for gc in caches]
    with pytest.raises(ValueError, match="length"):
        decode_step(cfg, params, jnp.zeros((2,), jnp.int32), ragged)
