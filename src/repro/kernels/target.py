"""Which device the kernels are built for.

One place answers "is the default backend a TPU?", so every kernel wrapper
resolves ``interpret=None`` the same way: compiled Mosaic kernels on a
TPU, the Pallas interpreter everywhere else.
"""
from __future__ import annotations

from typing import Optional

import jax


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``None`` -> interpret the kernel bodies unless running on a TPU."""
    return (not on_tpu()) if interpret is None else interpret
