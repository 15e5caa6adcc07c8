"""jit'd public wrappers for the dma_copy kernels."""
from __future__ import annotations

from functools import partial

import jax

from .. import interpret_mode
from .kernel import dma_copy_explicit, dma_copy_pipelined

__all__ = ["dma_copy"]


@partial(jax.jit, static_argnames=("mode", "block_rows"))
def dma_copy(x, mode: str = "pipelined", block_rows: int = 256):
    interp = interpret_mode()
    if mode == "explicit":
        return dma_copy_explicit(x, block_rows=block_rows, interpret=interp)
    return dma_copy_pipelined(x, block_rows=block_rows, interpret=interp)
