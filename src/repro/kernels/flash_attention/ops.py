"""jit'd public wrapper for the flash attention kernel.

On the CPU backend the kernel body executes in interpret mode; on TPU it
compiles to Mosaic.  The oracle is ``ref.flash_attention_ref``.
"""
from __future__ import annotations

from functools import partial

import jax

from .. import interpret_mode
from .kernel import flash_attention_pallas

__all__ = ["flash_attention"]


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    return flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=interpret_mode())
