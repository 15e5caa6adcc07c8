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

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dma_copy.kernel import dma_copy_explicit, dma_copy_pipelined
from repro.configs import ARCHS
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.paged_attention.kernel import paged_attention_pallas
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


@pytest.mark.parametrize("hq,hk,hd,L,slots,n_blk", [
    (32, 32, 128, 10, 7, 32), (32, 8, 128, 12, 6, 131), (8, 1, 256, 18, 8, 32),
], ids=["deepseek-7b-l10", "qwen3-8b-l12", "gemma-2b"])
def test_paged_attention_served_widths(one_chip, hq, hk, hd, L, slots, n_blk):
    # the decode launch's kernel: whole page pools of 64-token pages
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((L, 1 + slots * n_blk, 64, hk, hd), jnp.bfloat16)
    # also under the highest default precision, as chip_smoke.py checks it
    for precision in ("default", "highest"):
        with jax.default_matmul_precision(precision):
            _compile(paged_attention_pallas,
                     sds((slots, hq, hd), jnp.bfloat16), pool, pool,
                     sds((slots, n_blk), jnp.int32),
                     sds((slots,), jnp.int32), sds((), jnp.int32))


_RESULT = re.compile(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]")
_OP = re.compile(r"\s*(?:ROOT )?%\S+ = (.*?) ([a-z][\w-]*)\(")
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")

_SERVED = pytest.mark.parametrize("arch,layers,slots,max_seq", [
    ("deepseek-7b", 10, 7, 2048), ("qwen3-8b", 12, 6, 8384),
], ids=["deepseek-7b-l10", "qwen3-8b-l12"])


def _size(dims: str) -> int:
    size = 1
    for d in dims.split(","):
        if d:
            size *= int(d)
    return size


@pytest.fixture(scope="module")
def served_launch(one_chip):
    """``(arch, layers, slots, max_seq, launch) -> (compiled, params,
    pool)``: the engine's decode or extend launch at a cell's widths, slots
    and 64-token pages, lowered for the described chip with the paged
    kernel compiled, not interpreted.  The launch itself is lowered, not a
    jit around it, which would drop its donation of the pools.  Compiled
    once for the module."""
    from repro.runtime.server import ContinuousBatchingServer

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    @functools.cache
    def get(arch, layers, slots, max_seq, launch):
        cfg = dataclasses.replace(ARCHS[arch], n_layers=layers,
                                  tie_embeddings=False)
        box = {}

        def build():
            eng = ContinuousBatchingServer(
                cfg, batch_size=slots, max_seq=max_seq, tokens_per_launch=4,
                seed=0, kv_page_tokens=64, prefill_chunk=512)
            box["kv"] = eng.kv
            return eng.params, eng.kv.k_pool

        params, pool = jax.eval_shape(build)
        kv = box["kv"]
        i32 = jnp.int32
        if launch == "decode":
            fn, rest = kv._decode_slots.__wrapped__, (
                jax.ShapeDtypeStruct((slots, kv.n_blk), i32),
                jax.ShapeDtypeStruct((slots,), i32),
                jax.ShapeDtypeStruct((slots, 1, 1), i32))
        else:
            fn, rest = kv._extend.__wrapped__, (
                jax.ShapeDtypeStruct((kv.n_blk,), i32),
                jax.ShapeDtypeStruct((), i32),
                jax.ShapeDtypeStruct((1, 512), i32))
        # the host is a CPU: compile the kernel for the chip
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(paged_ops, "interpret_mode", lambda: False)
            compiled = fn.lower(*on_chip((params, pool, pool) + rest)).compile()
        return compiled, params, pool

    return get


@_SERVED
def test_paged_decode_launch_reads_pages_in_place(served_launch, arch,
                                                  layers, slots, max_seq):
    """The benchmark's decode launch (``decode_paged``) at its cells'
    widths, slots, positions and 64-token pages, on the kernel path: the
    kernel is in the program, and nothing it computes is as large as one
    slot's whole [L, S, Hkv, hd] cache besides the weights and the page
    pools themselves (the donated pools, which the scatter updates in
    place).  The gather of every slot's block table made such a cache per
    slot."""
    compiled, params, pool = served_launch(arch, layers, slots, max_seq,
                                           "decode")
    assert "tpu_custom_call" in compiled.as_text()
    L, P, pt, hk, hd = pool.shape
    slot_cache = L * max_seq * hk * hd
    allowed = {pool.shape, (L, P, pt * hk, hd)} | {
        x.shape for x in jax.tree_util.tree_leaves(params)}
    large = []
    for line in compiled.as_text().splitlines():
        m = _RESULT.match(line)
        if m:
            shape = tuple(int(d) for d in m.group(1).split(",") if d)
            if _size(m.group(1)) >= slot_cache and shape not in allowed:
                large.append(line.strip()[:160])
    assert not large, large


@_SERVED
@pytest.mark.parametrize("launch", ["decode", "extend"])
def test_paged_launches_update_donated_pools_in_place(
        served_launch, arch, layers, slots, max_seq, launch):
    """Both launches are given the K and V page pools to own: the compiled
    program aliases both to its outputs and copies neither.  Undonated,
    each launch first copied both pools whole before scattering its new
    rows (1.18 GB each for deepseek-7b-l10)."""
    compiled, _, pool = served_launch(arch, layers, slots, max_seq, launch)
    pool_bytes = pool.size * pool.dtype.itemsize
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * pool_bytes
    copies = []
    for line in compiled.as_text().splitlines():
        m = _OP.match(line)
        if m and m.group(2) in ("copy", "copy-start") and any(
                _size(d) >= pool.size for d in _SHAPE.findall(m.group(1))):
            copies.append(line.strip()[:160])
    assert not copies, copies
