"""Where the Pallas kernels run compiled and where they are interpreted."""
from __future__ import annotations

import jax


def interpret() -> bool:
    """True on the CPU backend, which has no Pallas TPU compiler: the
    kernel bodies are then interpreted (the tests' mode).  Everywhere
    else the kernels are compiled."""
    return jax.default_backend() == "cpu"
