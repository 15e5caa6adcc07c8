"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.kernels.dma_copy.ops import dma_copy
from repro.kernels.dma_copy.ref import dma_copy_ref

rng = np.random.default_rng(42)


# ---------------------------------------------------------------- flash
@pytest.mark.parametrize("B,S,H,hd", [
    (1, 128, 1, 64), (2, 256, 4, 64), (1, 256, 2, 128), (1, 128, 2, 256),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, hd, causal, dtype):
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, H, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, H, hd)), dtype)
    out = flash_attention(q, k, v, causal=causal)
    ref = flash_attention_ref(q, k, v, causal=causal)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_block_shapes():
    q = jnp.asarray(rng.normal(size=(1, 512, 2, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 512, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 512, 2, 64)), jnp.float32)
    ref = flash_attention_ref(q, k, v, causal=True)
    for bq, bk in [(64, 128), (128, 64), (256, 256)]:
        out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------- ssd
@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 64, 2, 16, 8, 16), (2, 128, 4, 32, 16, 32),
    (1, 256, 8, 64, 128, 64), (1, 128, 3, 16, 8, 128),
])
def test_ssd_scan_sweep(B, S, H, P, N, chunk):
    xh = jnp.asarray(rng.normal(size=(B, S, H, P)).astype(np.float32))
    dt = jnp.asarray(np.abs(rng.normal(size=(B, S, H))).astype(np.float32))
    A = jnp.asarray(-np.abs(rng.normal(size=(H,))).astype(np.float32))
    Bc = jnp.asarray(rng.normal(size=(B, S, N)).astype(np.float32))
    Cc = jnp.asarray(rng.normal(size=(B, S, N)).astype(np.float32))
    y, _ = ssd_scan(xh, dt, A, Bc, Cc, chunk=min(chunk, S))
    y_ref, _ = ssd_scan_ref(xh, dt, A, Bc, Cc, chunk=min(chunk, S))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-3, atol=1e-3)


def test_ssd_scan_bf16():
    B, S, H, P, N = 1, 64, 2, 16, 8
    xh = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.bfloat16)
    dt = jnp.asarray(np.abs(rng.normal(size=(B, S, H))).astype(np.float32))
    A = jnp.asarray(-np.abs(rng.normal(size=(H,))).astype(np.float32))
    Bc = jnp.asarray(rng.normal(size=(B, S, N)), jnp.bfloat16)
    Cc = jnp.asarray(rng.normal(size=(B, S, N)), jnp.bfloat16)
    y, _ = ssd_scan(xh, dt, A, Bc, Cc, chunk=16)
    y_ref, _ = ssd_scan_ref(xh, dt, A, Bc, Cc, chunk=16)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=6e-2, atol=6e-2)


# ---------------------------------------------------------------- dma
@pytest.mark.parametrize("mode", ["pipelined", "explicit"])
@pytest.mark.parametrize("R,C,blk", [(256, 64, 64), (1024, 128, 256),
                                     (128, 32, 128)])
def test_dma_copy_sweep(mode, R, C, blk):
    x = jnp.asarray(rng.normal(size=(R, C)).astype(np.float32))
    y = dma_copy(x, mode=mode, block_rows=blk)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(dma_copy_ref(x)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_dma_copy_dtypes(dtype):
    x = jnp.asarray(rng.integers(-100, 100, size=(256, 128)), dtype)
    y = dma_copy(x, mode="pipelined", block_rows=64)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


# ---------------------------------------------------------------- rms_norm
from repro.kernels.rms_norm.ops import rms_norm_fused
from repro.kernels.rms_norm.ref import rms_norm_ref


@pytest.mark.parametrize("shape", [(4, 64, 256), (2, 100, 128), (1, 7, 512),
                                   (8, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rms_norm_fused_sweep(shape, dtype):
    x = jnp.asarray(rng.normal(size=shape), dtype)
    s = jnp.asarray(rng.normal(size=shape[-1:]) * 0.1, dtype)
    out = rms_norm_fused(x, s)
    ref = rms_norm_ref(x, s)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_rms_norm_matches_model_layer():
    from repro.models.layers import rms_norm as model_rms
    x = jnp.asarray(rng.normal(size=(3, 17, 64)).astype(np.float32))
    s = jnp.asarray(rng.normal(size=(64,)).astype(np.float32) * 0.1)
    out = rms_norm_fused(x, s)
    ref = model_rms({"scale": s}, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------- paged
from repro.kernels.paged_attention.kernel import NEG_INF
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref

PT = 8                                   # tokens a page
# one slot for each read length: none, one position, a partial page, a
# whole page, the whole table.  Page 0 is the scratch page every unused
# table row points at; page 3 is shared by two slots, page 5 by three.
PAGED_N = [0, 1, PT + 3, PT, 4 * PT]
PAGED_TABLES = [[0, 0, 0, 0], [3, 0, 0, 0], [3, 5, 0, 0], [5, 0, 0, 0],
                [2, 5, 6, 1]]


@pytest.mark.parametrize("hq,hk,hd", [(32, 32, 128), (32, 8, 128),
                                      (8, 1, 256)],
                         ids=["mha", "gqa", "mqa"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(hq, hk, hd, dtype):
    """Every read length, shared and scratch pages, layer 1 of 2, and a
    panel of two pages, so the longer slots take several double-buffered
    steps."""
    L, P = 2, 7
    k_pool = jnp.asarray(rng.normal(size=(L, P, PT, hk, hd)), dtype)
    v_pool = jnp.asarray(rng.normal(size=(L, P, PT, hk, hd)), dtype)
    q = jnp.asarray(rng.normal(size=(len(PAGED_N), hq, hd)), dtype)
    tables = jnp.asarray(PAGED_TABLES, jnp.int32)
    n = jnp.asarray(PAGED_N, jnp.int32)
    acc, m, l = paged_attention(q, k_pool, v_pool, tables, n, jnp.int32(1),
                                block_rows=2 * PT * hk)
    r_acc, r_m, r_l = paged_attention_ref(q, k_pool, v_pool, tables, n, 1)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    live = np.asarray(PAGED_N) > 0
    out = np.asarray(acc / l[..., None])[live]
    ref = np.asarray(r_acc / r_l[..., None])[live]
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(m), np.asarray(r_m),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(l), np.asarray(r_l),
                               rtol=tol, atol=tol)
    # a slot with nothing to read merges as empty: no weight, no NaN
    assert not np.asarray(acc)[~live].any()
    assert not np.asarray(l)[~live].any()
    assert (np.asarray(m)[~live] == NEG_INF).all()
