"""Compile every Pallas kernel at real widths for one described TPU v5e chip.

The TPU compiler is installed with jax, and it compiles for a chip that is
described rather than attached, so these run without a TPU.  Interpret-mode
tests cannot see what this catches: block shapes the chip's tiling refuses,
kernels that exceed VMEM, primitives Mosaic cannot lower.  Each test asserts
that the kernel reached the compiled program as a Mosaic custom call.

The topology is described inside a fixture, never at import time: only one
process at a time may load the TPU library, and test collection must not
depend on it.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dma_copy.kernel import dma_copy_explicit, dma_copy_pipelined
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.rms_norm.kernel import rms_norm_pallas
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: a compile
    for a described chip is written to the cache but cannot be read back
    without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_gemma_2b_widths(one_chip):
    # Gemma-2B attention: 8 heads of 256 at S=4096
    qkv = jax.ShapeDtypeStruct((1, 4096, 8, 256), jnp.bfloat16,
                               sharding=one_chip)
    _compile(lambda q, k, v: flash_attention_pallas(q, k, v, causal=True),
             qkv, qkv, qkv)


def test_rms_norm_4096x2048(one_chip):
    x = jax.ShapeDtypeStruct((4096, 2048), jnp.bfloat16, sharding=one_chip)
    s = jax.ShapeDtypeStruct((2048,), jnp.bfloat16, sharding=one_chip)
    _compile(rms_norm_pallas, x, s)


@pytest.mark.parametrize("copy", [dma_copy_pipelined, dma_copy_explicit],
                         ids=["pipelined", "explicit"])
def test_dma_copy_8192x2048(one_chip, copy):
    x = jax.ShapeDtypeStruct((8192, 2048), jnp.bfloat16, sharding=one_chip)
    _compile(copy, x)


def test_ssd_scan_mamba2_780m_widths(one_chip):
    # mamba2-780m SSD: H=48 heads of P=64, state N=128, chunk 256
    B, S, H, P, N = 1, 4096, 48, 64, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compile(lambda x, dt, a, b, c: ssd_scan_pallas(x, dt, a, b, c,
                                                    chunk=256),
             sds((B, S, H, P), jnp.bfloat16), sds((B, S, H), jnp.float32),
             sds((H,), jnp.float32), sds((B, S, N), jnp.bfloat16),
             sds((B, S, N), jnp.bfloat16))
