"""Compiles for a described TPU v5e chip: the four Pallas kernels at one
real-width shape each, and the phi3-mini bf16 decode step.

Nothing runs: the TPU compiler refuses what a chip would refuse (block
shapes, unlowered primitives, device memory), so these guard the chip
path from a CPU host.  The topology is described inside a fixture, never
at import, so only the worker that runs this file loads the TPU library.
"""
import dataclasses

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
