"""``batch.decode_roofline``'s reading in a minicpm-2b cell: the share of
the roofline reached by ``jit_decode_step``, which decodes every one of
the 16 rows in one call and reads the tied 122,753-row head (the
embedding) once a call, as ``flops.decoder_weight_bytes`` counts it."""
from pathlib import Path

from bench.common.harness import load_module

read = load_module(Path(__file__).with_name("batch.decode_roofline.py")).read
