"""jit'd public wrapper for the fused RMSNorm kernel."""
from __future__ import annotations

from functools import partial

import jax

from .. import interpret_mode
from .kernel import rms_norm_pallas

__all__ = ["rms_norm_fused"]


@partial(jax.jit, static_argnames=("eps", "block_rows"))
def rms_norm_fused(x, scale, eps: float = 1e-6, block_rows: int = 128):
    return rms_norm_pallas(x, scale, eps=eps, block_rows=block_rows,
                           interpret=interpret_mode())
