"""Pallas TPU kernels for the perf-critical compute hot spots.

Each subpackage ships kernel.py (pl.pallas_call + explicit BlockSpec VMEM
tiling), ops.py (jitted wrapper), and ref.py (pure-jnp oracle used by the
allclose test sweeps).  The wrappers compile the kernels for the TPU and
interpret them on the CPU backend (``platform.interpret``), where the
tests run them.
"""
from .flash_attention.ops import flash_attention
from .decode_attention.ops import decode_attention
from .rglru_scan.ops import rglru_scan
from .moe_gating.ops import moe_gating

__all__ = ["flash_attention", "decode_attention", "rglru_scan", "moe_gating"]
