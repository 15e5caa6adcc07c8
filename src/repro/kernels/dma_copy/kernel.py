"""Controlled DMA issuance — the paper's §5.3/§6.2 adapted to TPU.

The paper bypasses CUDA and programs the GPU copy engine directly by writing
DMA descriptors into the pushbuffer, measuring raw engine behaviour without
driver overhead.  The TPU analogue of "programming the copy engine" is
issuing explicit async HBM↔VMEM copies from a Pallas kernel:

* ``dma_copy_explicit`` keeps src/dst in ``ANY`` (HBM) memory space and
  moves each tile with ``pltpu.make_async_copy`` + DMA semaphores — the
  descriptors we write *are* the TPU's DMA commands (start/wait = the
  submit/semaphore protocol of §4.3);
* ``dma_copy_pipelined`` expresses the same transfer through BlockSpec
  pipelining, letting the Pallas pipeline emitter double-buffer the DMA —
  the "driver-chosen" path to compare against.

Sweeping tile sizes over both paths is the Figure-6 analogue: startup cost
vs saturation bandwidth of the copy path under explicit vs automatic
submission.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["dma_copy_pipelined", "dma_copy_explicit"]


def _pipelined_kernel(src_ref, dst_ref):
    dst_ref[...] = src_ref[...]


def dma_copy_pipelined(x: jax.Array, block_rows: int = 256,
                       interpret: bool = False) -> jax.Array:
    """[R, C] HBM→HBM copy, tiles auto-pipelined through VMEM."""
    R, C = x.shape
    block_rows = min(block_rows, R)
    assert R % block_rows == 0
    return pl.pallas_call(
        _pipelined_kernel,
        grid=(R // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), x.dtype),
        interpret=interpret,
    )(x)


def _explicit_kernel(src_hbm, dst_hbm, vmem, sem_in, sem_out,
                     *, block_rows: int):
    i = pl.program_id(0)
    rows = pl.dslice(i * block_rows, block_rows)
    copy_in = pltpu.make_async_copy(src_hbm.at[rows], vmem, sem_in)
    copy_in.start()
    copy_in.wait()
    copy_out = pltpu.make_async_copy(vmem, dst_hbm.at[rows], sem_out)
    copy_out.start()
    copy_out.wait()


def dma_copy_explicit(x: jax.Array, block_rows: int = 256,
                      interpret: bool = False) -> jax.Array:
    """[R, C] HBM→HBM copy with hand-written DMA descriptors."""
    R, C = x.shape
    block_rows = min(block_rows, R)
    assert R % block_rows == 0
    kernel = functools.partial(_explicit_kernel, block_rows=block_rows)
    return pl.pallas_call(
        kernel,
        grid=(R // block_rows,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((R, C), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_rows, C), x.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(x)
