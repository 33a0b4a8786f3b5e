"""Compiles for a described TPU v5e chip: the four Pallas kernels at one
real-width shape each, the phi3-mini bf16 decode step, and the serving
engine's donated ragged decode over its stacked cache.

Nothing runs: the TPU compiler refuses what a chip would refuse (block
shapes, unlowered primitives, device memory), so these guard the chip
path from a CPU host.  The topology is described inside a fixture, never
at import, so only the worker that runs this file loads the TPU library.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.kernel import decode_attention_fwd
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.moe_gating.kernel import moe_gating_fwd
from repro.kernels.rglru_scan.kernel import rglru_scan_fwd
from repro.models import transformer

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back here:
    # keep the persistent cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# one real-width shape per kernel: phi3-mini prefill / decode attention,
# recurrentgemma-2b's RG-LRU width, deepseek-v2's router
KERNELS = {
    "flash_attention": (
        lambda q, k, v: flash_attention_fwd(q, k, v),
        [((1, 32, 2048, 96), jnp.bfloat16)] * 3),
    "decode_attention": (
        lambda q, k, v, n: decode_attention_fwd(q, k, v, n),
        [((4, 32, 96), jnp.bfloat16), ((4, 2048, 32, 96), jnp.bfloat16),
         ((4, 2048, 32, 96), jnp.bfloat16), ((4,), jnp.int32)]),
    "rglru_scan": (
        lambda x, a, h: rglru_scan_fwd(x, a, h),
        [((1, 2048, 2560), jnp.bfloat16), ((1, 2048, 2560), jnp.bfloat16),
         ((1, 2560), jnp.float32)]),
    "moe_gating": (
        lambda logits: moe_gating_fwd(logits, top_k=6, capacity=192),
        [((4096, 160), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    args = [_spec(one_chip, s, d) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # Mosaic, not interpret


def test_phi3_bf16_decode_step_compiles_for_v5e(one_chip):
    cfg = dataclasses.replace(get_config("phi3-mini-3.8b"),
                              param_dtype="bfloat16")
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: _spec(one_chip, s.shape, s.dtype), tree)
    params = on_chip(transformer.param_specs(cfg))
    caches = on_chip(transformer.cache_specs(cfg, 4, 2048))
    token = _spec(one_chip, (4,), jnp.int32)
    compiled = jax.jit(
        lambda p, t, c: transformer.decode_step(cfg, p, t, c)
    ).lower(params, token, caches).compile()
    mem = compiled.memory_analysis()
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(params))
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < V5E_HBM_BYTES)


@pytest.mark.parametrize("program", ["_decode", "_prefill"])
def test_engine_rows_programs_alias_their_cache_on_v5e(one_chip, program):
    """The serving engine's decode step and prefill, each donated the
    phi3-mini stacked cache of 8 rows x 1280 tokens in bf16 (the prefill
    a 1024-token prompt, written over one row): the cache is written in
    place (its 4.03 GB aliased, no whole-cache copy in the program), and
    the weights, the cache and the temporaries fit the chip."""
    from repro.serving import engine

    cfg = dataclasses.replace(get_config("phi3-mini-3.8b"),
                              param_dtype="bfloat16")
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: _spec(one_chip, s.shape, s.dtype), tree)
    params = on_chip(transformer.param_specs(cfg))
    caches = on_chip(jax.eval_shape(
        lambda: transformer.rows_cache(cfg, 8, 1280)))
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(caches))
    assert cache_bytes > 4.02e9
    if program == "_decode":
        args = (_spec(one_chip, (8,), jnp.int32), caches)
    else:
        args = (_spec(one_chip, (1, 1024), jnp.int32), 1280, caches,
                _spec(one_chip, (), jnp.int32))
    compiled = getattr(engine, program).lower(cfg, params, *args).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes - 2**20   # lengths aside
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes
            < V5E_HBM_BYTES)
    whole = r"bf16\[32,8,1280,32,96\]\{[^}]*\} copy\("
    assert not re.search(whole, compiled.as_text())


@pytest.mark.parametrize("program", ["_decode", "_prefill"])
def test_minicpm_rows_programs_fit_v5e(one_chip, program):
    """minicpm-2b's decode step and prefill over 16 rows x 1280 tokens in
    bf16 (7.55 GB of cache beside 5.45 GB of weights): the cache is
    written in place, everything fits the chip, and the tied head reads
    the embedding where it lies (no copy or transpose of the 122,753 x
    2304 table)."""
    from repro.serving import engine

    cfg = dataclasses.replace(get_config("minicpm-2b"), param_dtype="bfloat16")
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: _spec(one_chip, s.shape, s.dtype), tree)
    params = on_chip(transformer.param_specs(cfg))
    assert "lm_head" not in params
    caches = on_chip(jax.eval_shape(
        lambda: transformer.rows_cache(cfg, 16, 1280)))
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(caches))
    if program == "_decode":
        args = (_spec(one_chip, (16,), jnp.int32), caches)
    else:
        args = (_spec(one_chip, (1, 1024), jnp.int32), 1280, caches,
                _spec(one_chip, (), jnp.int32))
    compiled = getattr(engine, program).lower(cfg, params, *args).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes - 2**20   # lengths aside
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes
            < V5E_HBM_BYTES)
    text = compiled.as_text()
    assert not re.search(r"bf16\[40,16,1280,36,64\]\{[^}]*\} copy\(", text)
    assert not re.search(r"bf16\[(122753,2304|2304,122753)\]\{[^}]*\} "
                         r"(copy|transpose)\(", text)
