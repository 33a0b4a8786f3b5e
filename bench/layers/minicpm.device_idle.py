"""``chat.device_idle``'s reading in a minicpm-2b cell: the share of the
traced window in which no operation ran on the device."""
from pathlib import Path

from bench.common.harness import load_module

read = load_module(Path(__file__).with_name("chat.device_idle.py")).read
