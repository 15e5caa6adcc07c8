"""Distributed paths that run on a single device: smap MoE fallback,
triangle attention equivalence, PP decode schedule math, elastic planning.

(The multi-device shard_map/PP correctness tests live in
``tests/test_multidevice.py`` and run in a subprocess with 8 fake devices —
the main pytest process must keep the default single-device backend.)
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKE_ARCHS
from repro.models.attention import (dense_causal_attention,
                                    triangle_chunked_attention)
from repro.models.moe import init_moe, moe_sorted, moe_sorted_smap

rng = np.random.default_rng(11)
KEY = jax.random.PRNGKey(0)


def test_triangle_attention_matches_dense():
    B, S, H, hd = 2, 256, 3, 32
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, H, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, H, hd)).astype(np.float32))
    ref = dense_causal_attention(q, k, v, causal=True)
    for chunk in (32, 64, 128):
        out = triangle_chunked_attention(q, k, v, chunk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)


def test_triangle_attention_odd_chunks_falls_back():
    B, S, H, hd = 1, 96, 2, 16   # n = 3 (odd) -> masked fallback
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)).astype(np.float32))
    out = triangle_chunked_attention(q, q, q, 32)
    ref = dense_causal_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def test_triangle_attention_halves_flops():
    from repro.core import capture_fn
    from repro.models.attention import chunked_causal_attention
    spec = jax.ShapeDtypeStruct((1, 2048, 2, 64), jnp.bfloat16)
    a = capture_fn(lambda q, k, v: chunked_causal_attention(q, k, v, 256),
                   spec, spec, spec)
    b = capture_fn(lambda q, k, v: triangle_chunked_attention(q, k, v, 256),
                   spec, spec, spec)
    assert b.flops / a.flops < 0.62          # (n+1)/2n + eps, n=8


def test_moe_smap_falls_back_without_mesh():
    from repro.distributed import context
    context.set_mesh(None, ())
    cfg = dataclasses.replace(SMOKE_ARCHS["qwen2-moe-a2.7b"],
                              n_shared_experts=0, capacity_factor=2.0)
    p = init_moe(KEY, cfg, jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 16, cfg.d_model)).astype(np.float32))
    y1, _ = moe_sorted(p, cfg, x)
    y2, _ = moe_sorted_smap(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y1),
                               rtol=1e-5, atol=1e-5)


def test_pp_decoder_schedule_math():
    """Stage/µbatch bookkeeping invariants (no devices needed)."""
    from repro.launch.mesh import SINGLE_POD
    n_stages = SINGLE_POD[0]
    n_micro = n_stages
    served = {}
    for t in range(n_micro):
        for s in range(n_stages):
            mb = (t - s) % n_micro
            served.setdefault(s, []).append(mb)
    for s, mbs in served.items():
        assert sorted(mbs) == list(range(n_micro))  # every stage: all µbs
    # µb m reaches stage s at tick (m+s) mod n_micro, wrapped iff m+s >= n
    for m in range(n_micro):
        for s in range(n_stages):
            t = (m + s) % n_micro
            assert (t - s) % n_micro == m
            assert (t < s) == (m + s >= n_micro)   # the pos_tok offset rule


def test_compile_cache_location(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise <repo>/.jax_cache,
    a fixed path that git ignores."""
    from repro.launch.mesh import REPO_COMPILE_CACHE, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(REPO_COMPILE_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(REPO_COMPILE_CACHE)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = REPO_COMPILE_CACHE.parent
    assert (root / "src" / "repro" / "launch" / "mesh.py").is_file()
    with open(root / ".gitignore") as f:
        assert ".jax_cache/" in f.read().split()
