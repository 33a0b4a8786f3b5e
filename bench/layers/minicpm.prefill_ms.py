"""``chat.prefill_ms``'s reading in a minicpm-2b cell: the median device
time of one ``jit_prefill`` call, its row's write into the stacked cache
included."""
from pathlib import Path

from bench.common.harness import load_module

read = load_module(Path(__file__).with_name("chat.prefill_ms.py")).read
