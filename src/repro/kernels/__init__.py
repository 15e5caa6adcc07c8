"""Pallas TPU kernels. Each kernel ships kernel.py (pl.pallas_call +
BlockSpec VMEM tiling), ops.py (jit'd wrapper, interpret on CPU), and
ref.py (pure-jnp oracle used by the shape/dtype sweep tests)."""
from __future__ import annotations

import jax

__all__ = ["interpret_mode"]


def interpret_mode() -> bool:
    """True only on the CPU backend, where Pallas kernels run interpreted.

    Every other backend compiles the kernel.  Errors from backend discovery
    propagate: a missing accelerator must not silently become interpret
    mode.
    """
    return jax.default_backend() == "cpu"
