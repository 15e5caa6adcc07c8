"""jit'd public wrapper for the paged decode attention kernel.

On the CPU backend the kernel body executes in interpret mode; on TPU it
compiles to Mosaic.  The oracle is ``ref.paged_attention_ref``.
"""
from __future__ import annotations

from functools import partial

import jax

from .. import interpret_mode
from .kernel import BLOCK_ROWS, paged_attention_pallas

__all__ = ["paged_attention"]


@partial(jax.jit, static_argnames=("block_rows",))
def paged_attention(q, k_pool, v_pool, tables, n, layer,
                    block_rows: int = BLOCK_ROWS):
    return paged_attention_pallas(q, k_pool, v_pool, tables, n, layer,
                                  block_rows=block_rows,
                                  interpret=interpret_mode())
