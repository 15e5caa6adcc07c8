"""Attention: GQA/MQA with rotary, chunked-causal (flash-style) prefill/train
path and KV-cache decode path.

The chunked causal path is the pure-jnp oracle of the Pallas flash kernel
(``repro.kernels.flash_attention``); which implementation runs is selected by
``impl`` ("ref" on CPU/dry-run, "pallas" on real TPU).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import Params, apply_rope, init_rms_norm, rms_norm, rotary

__all__ = ["init_attention", "attention", "decode_attention",
           "decode_attention_paged", "init_kv_cache",
           "chunked_causal_attention", "dense_causal_attention",
           "extend_attention", "gather_block_table", "scatter_block_rows"]


def init_attention(key: jax.Array, cfg: ModelConfig, dtype) -> Params:
    d, h, hk, hd = cfg.d_model, cfg.n_heads_padded, cfg.n_kv_heads, cfg.hd
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": (jax.random.normal(kq, (d, h, hd), jnp.float32) * s).astype(dtype),
        "wk": (jax.random.normal(kk, (d, hk, hd), jnp.float32) * s).astype(dtype),
        "wv": (jax.random.normal(kv, (d, hk, hd), jnp.float32) * s).astype(dtype),
        "wo": (jax.random.normal(ko, (h, hd, d), jnp.float32)
               * ((h * hd) ** -0.5)).astype(dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rms_norm(hd, dtype)
        p["k_norm"] = init_rms_norm(hd, dtype)
    return p


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    B, S, Hk, hd = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (B, S, Hk, n_rep, hd))
    return k.reshape(B, S, Hk * n_rep, hd)


def dense_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = True) -> jax.Array:
    """Reference O(S^2)-memory attention. q,k,v: [B, S, H, hd]."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    scale = hd ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        scores = jnp.where(mask[None, None], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def chunked_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             chunk: int = 1024, causal: bool = True
                             ) -> jax.Array:
    """Flash-style streaming softmax over KV chunks: O(S·chunk) memory.

    This is the jnp oracle for the Pallas kernel.  q,k,v: [B, S, H, hd].
    """
    B, S, H, hd = q.shape
    if S % chunk or S <= chunk:
        return dense_causal_attention(q, k, v, causal)
    n = S // chunk
    scale = hd ** -0.5
    qc = jnp.moveaxis(q.reshape(B, n, chunk, H, hd), 1, 0)
    kc = jnp.moveaxis(k.reshape(B, n, chunk, H, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, n, chunk, H, hd), 1, 0)

    neg = jnp.finfo(jnp.float32).min

    def process_q_chunk(qi_idx_and_q):
        qi, q_i = qi_idx_and_q
        # running accumulators over kv chunks
        acc0 = jnp.zeros((B, chunk, H, hd), jnp.float32)
        m0 = jnp.full((B, chunk, H), neg, jnp.float32)
        l0 = jnp.zeros((B, chunk, H), jnp.float32)

        def kv_body(carry, kj_and_kv):
            acc, m, l = carry
            kj, k_j, v_j = kj_and_kv
            s = jnp.einsum("bqhd,bkhd->bqhk", q_i, k_j).astype(jnp.float32) * scale
            if causal:
                q_pos = qi * chunk + jnp.arange(chunk)
                k_pos = kj * chunk + jnp.arange(chunk)
                mask = q_pos[:, None] >= k_pos[None, :]
                s = jnp.where(mask[None, :, None, :], s, neg)
                # chunks fully in the future contribute nothing
                s = jnp.where(kj <= qi, s, neg)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bqhk,bkhd->bqhd", p.astype(v_j.dtype), v_j).astype(jnp.float32)
            return (acc_new, m_new, l_new), ()

        (acc, m, l), _ = jax.lax.scan(
            kv_body, (acc0, m0, l0), (jnp.arange(n), kc, vc))
        return (acc / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)

    out = jax.lax.map(process_q_chunk, (jnp.arange(n), qc))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, hd)


def triangle_chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                               chunk: int = 1024) -> jax.Array:
    """Causal chunked attention touching ONLY the n(n+1)/2 causal pairs.

    The plain chunked path (above) runs all n² (q-chunk, kv-chunk) pairs and
    masks the future half — 2× wasted MXU work and 2× wasted chunk-buffer
    traffic.  Folding row r with row n-1-r gives every folded row a uniform
    kv trip count of n+1, so a rectangular scan covers exactly the causal
    triangle: FLOPs and interior HBM traffic drop ~2× with bit-identical
    results.  (A beyond-paper optimization; see EXPERIMENTS.md §Perf.)
    """
    B, S, H, hd = q.shape
    n = S // chunk
    if n * chunk != S or n < 2 or n % 2:
        return chunked_causal_attention(q, k, v, chunk)
    scale = hd ** -0.5
    neg = jnp.finfo(jnp.float32).min
    qc = jnp.moveaxis(q.reshape(B, n, chunk, H, hd), 1, 0)
    kc = jnp.moveaxis(k.reshape(B, n, chunk, H, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, n, chunk, H, hd), 1, 0)

    def row_fn(r):
        # folded pair: q chunk r ("lo", needs kv 0..r) and q chunk n-1-r
        # ("hi", needs kv 0..n-1-r); together exactly n+1 kv steps.
        q_lo = qc[r]
        q_hi = qc[n - 1 - r]
        hi_idx = n - 1 - r

        def body(carry, t):
            acc_lo, m_lo, l_lo, acc_hi, m_hi, l_hi = carry
            serve_lo = t <= r
            kv_idx = jnp.where(serve_lo, t, t - (r + 1))
            k_t = jax.lax.dynamic_index_in_dim(kc, kv_idx, 0, keepdims=False)
            v_t = jax.lax.dynamic_index_in_dim(vc, kv_idx, 0, keepdims=False)
            q_sel = jnp.where(serve_lo, q_lo, q_hi)        # elementwise select
            s = jnp.einsum("bqhd,bkhd->bqhk", q_sel,
                           k_t).astype(jnp.float32) * scale
            # mask only the diagonal block of whichever row is served
            q_row = jnp.where(serve_lo, r, hi_idx)
            on_diag = kv_idx == q_row
            q_pos = jnp.arange(chunk)[:, None]
            k_pos = jnp.arange(chunk)[None, :]
            diag_mask = (q_pos >= k_pos) | (~on_diag)
            s = jnp.where(diag_mask[None, :, None, :], s, neg)
            m_prev = jnp.where(serve_lo, m_lo, m_hi)
            l_prev = jnp.where(serve_lo, l_lo, l_hi)
            acc_prev = jnp.where(serve_lo, acc_lo, acc_hi)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p_ = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p_, axis=-1)
            acc_new = acc_prev * alpha[..., None] + jnp.einsum(
                "bqhk,bkhd->bqhd", p_.astype(v_t.dtype),
                v_t).astype(jnp.float32)
            acc_lo = jnp.where(serve_lo, acc_new, acc_lo)
            m_lo = jnp.where(serve_lo, m_new, m_lo)
            l_lo = jnp.where(serve_lo, l_new, l_lo)
            acc_hi = jnp.where(serve_lo, acc_hi, acc_new)
            m_hi = jnp.where(serve_lo, m_hi, m_new)
            l_hi = jnp.where(serve_lo, l_hi, l_new)
            return (acc_lo, m_lo, l_lo, acc_hi, m_hi, l_hi), ()

        z = jnp.zeros((B, chunk, H, hd), jnp.float32)
        m0 = jnp.full((B, chunk, H), neg, jnp.float32)
        l0 = jnp.zeros((B, chunk, H), jnp.float32)
        (acc_lo, m_lo, l_lo, acc_hi, m_hi, l_hi), _ = jax.lax.scan(
            body, (z, m0, l0, z, m0, l0), jnp.arange(n + 1))
        out_lo = (acc_lo / jnp.maximum(l_lo[..., None], 1e-30)).astype(q.dtype)
        out_hi = (acc_hi / jnp.maximum(l_hi[..., None], 1e-30)).astype(q.dtype)
        return out_lo, out_hi

    lo, hi = jax.lax.map(row_fn, jnp.arange(n // 2))
    # lo rows are q chunks 0..n/2-1; hi rows are q chunks n-1..n/2
    out = jnp.concatenate([lo, hi[::-1]], axis=0)          # [n, B, c, H, hd]
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, hd)


def attention(p: Params, cfg: ModelConfig, x: jax.Array,
              positions: Optional[jax.Array] = None, causal: bool = True,
              impl: str = "ref",
              kv_override: Optional[Tuple[jax.Array, jax.Array]] = None
              ) -> jax.Array:
    """Full-sequence attention (train / prefill). x: [B, S, D] -> [B, S, D].

    ``kv_override`` supplies externally computed K/V (cross-attention).
    """
    B, S, D = x.shape
    h, hk, hd = cfg.n_heads_padded, cfg.n_kv_heads, cfg.hd
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if kv_override is None:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    else:
        k, v = kv_override
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q)
        k = rms_norm(p["k_norm"], k) if kv_override is None else k
    if cfg.pos_embed == "rope":
        if positions is None:
            positions = jnp.arange(S)[None, :]
        sin, cos = rotary(positions, hd, cfg.rope_theta)
        q = apply_rope(q, sin, cos)
        if kv_override is None:
            k = apply_rope(k, sin, cos)
    n_rep = h // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    if impl == "pallas":
        from ..kernels.flash_attention.ops import flash_attention
        out = flash_attention(q, k, v, causal=causal)
    elif causal and cfg.attn_chunk and S > cfg.attn_chunk:
        if cfg.attn_tri:
            out = triangle_chunked_attention(q, k, v, cfg.attn_chunk)
        else:
            out = chunked_causal_attention(q, k, v, cfg.attn_chunk, causal)
    else:
        out = dense_causal_attention(q, k, v, causal)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


# --------------------------------------------------------------------------
# decode path
# --------------------------------------------------------------------------
def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype,
                  n_layers: Optional[int] = None) -> Dict[str, jax.Array]:
    """KV cache [L, B, S, Hkv, hd] + current length."""
    L = n_layers if n_layers is not None else cfg.n_layers
    shape = (L, batch, max_seq, cfg.n_kv_heads, cfg.hd)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "length": jnp.zeros((), jnp.int32),
    }


def _decode_qkv(p: Params, cfg: ModelConfig, x: jax.Array, pos: jax.Array):
    """The query and the new K/V row of one token at position ``pos``: []
    for every row of x [B, 1, D], or [B], one per row."""
    hd = cfg.hd
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q)
        k_new = rms_norm(p["k_norm"], k_new)
    if cfg.pos_embed == "rope":
        sin, cos = rotary(jnp.reshape(pos, (-1, 1)), hd, cfg.rope_theta)
        q = apply_rope(q, sin, cos)
        k_new = apply_rope(k_new, sin, cos)
    return q, k_new, v_new


def decode_attention(p: Params, cfg: ModelConfig, x: jax.Array,
                     k_cache: jax.Array, v_cache: jax.Array,
                     length: jax.Array,
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token attention against a cache.

    x: [B, 1, D]; k_cache/v_cache: [B, S_max, Hkv, hd]; length: [] int32 —
    number of valid cache positions (the new token is written at ``length``).
    The query scores the whole cache; positions past ``length`` are masked
    to exactly zero weight.  Returns (out [B,1,D], new_k_cache, new_v_cache).
    """
    B, S = k_cache.shape[:2]
    h, hk, hd = cfg.n_heads_padded, cfg.n_kv_heads, cfg.hd
    q, k_new, v_new = _decode_qkv(p, cfg, x, length)
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k_new.astype(k_cache.dtype), (0, length, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v_new.astype(v_cache.dtype), (0, length, 0, 0))
    q_ = q.reshape(B, hk, h // hk, hd)
    scores = jnp.einsum("bgrd,bsgd->bgrs", q_, k_cache).astype(
        jnp.float32) * hd ** -0.5
    valid = jnp.arange(S) <= length
    scores = jnp.where(valid[None, None, None, :], scores,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrs,bsgd->bgrd", probs.astype(v_cache.dtype), v_cache)
    out = out.reshape(B, 1, h, hd)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), k_cache, v_cache


def decode_attention_paged(p: Params, cfg: ModelConfig, x: jax.Array,
                           k_pool: jax.Array, v_pool: jax.Array,
                           tables: jax.Array, layer: jax.Array,
                           rows_k: jax.Array, rows_v: jax.Array,
                           start: jax.Array, pos: jax.Array,
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token attention of every slot, reading the pages in place.

    x: [B, 1, D], slot b at position ``pos[b]``; k_pool/v_pool: [L, P,
    page_tokens, Hkv, hd], read only, of which ``layer`` is this layer;
    tables: [B, n_blk]; rows_k/rows_v: [B, T, Hkv, hd], the window standing
    for positions start..start+T-1 of each slot.  The new K/V row goes into
    the window where :func:`decode_attention` would write the cache
    (``pos`` clamped to S - 1).  The query then attends two parts,
    merged by log-sum-exp: positions [0, start) through the paged
    attention kernel, which copies only those pages, and the window rows
    up to ``min(pos, S - 1)``.  Scores and softmax are float32; a slot
    with ``start == 0`` (empty, or table all scratch page) gets the window
    alone.  Returns (out [B,1,D], rows_k, rows_v).
    """
    from ..kernels.paged_attention.ops import paged_attention
    B, T = rows_k.shape[:2]
    h, hk, hd = cfg.n_heads_padded, cfg.n_kv_heads, cfg.hd
    n_rep = h // hk
    S = tables.shape[1] * k_pool.shape[2]
    q, k_new, v_new = _decode_qkv(p, cfg, x, pos)
    here = (jnp.arange(T)[None, :]
            == (jnp.minimum(pos, S - 1) - start)[:, None])[:, :, None, None]
    rows_k = jnp.where(here, k_new.astype(rows_k.dtype), rows_k)
    rows_v = jnp.where(here, v_new.astype(rows_v.dtype), rows_v)
    acc, m, l = paged_attention(q[:, 0], k_pool, v_pool, tables, start, layer)
    acc = acc.reshape(B, hk, n_rep, hd)
    m, l = m.reshape(B, hk, n_rep), l.reshape(B, hk, n_rep)
    q_ = q.reshape(B, hk, n_rep, hd)
    s = jnp.einsum("bgrd,btgd->bgrt", q_, rows_k,
                   preferred_element_type=jnp.float32) * hd ** -0.5
    live = (start[:, None] + jnp.arange(T)[None, :]) <= pos[:, None]
    s = jnp.where(live[:, None, None, :], s, jnp.finfo(jnp.float32).min)
    m_all = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_all)
    pw = jnp.exp(s - m_all[..., None])
    l_all = alpha * l + jnp.sum(pw, axis=-1)
    acc = alpha[..., None] * acc + jnp.einsum(
        "bgrt,btgd->bgrd", pw.astype(rows_v.dtype), rows_v,
        preferred_element_type=jnp.float32)
    out = (acc / l_all[..., None]).astype(rows_v.dtype).reshape(B, 1, h, hd)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), rows_k, rows_v


def extend_attention(p: Params, cfg: ModelConfig, x: jax.Array,
                     k_cache: jax.Array, v_cache: jax.Array,
                     start: jax.Array,
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Prompt-chunk attention against a cache (chunked prefill).

    x: [B, C, D] — C new prompt positions starting at global position
    ``start`` ([] int32); k_cache/v_cache: [B, S_max, Hkv, hd] holding the
    first ``start`` positions.  The chunk's K/V are written at
    [start, start+C) and the chunk queries attend causally over the whole
    buffer (future positions hold zeros and are masked to exactly-zero
    softmax weight, so results are bit-identical to whole-prompt prefill).
    Returns (out [B,C,D], new_k_cache, new_v_cache).
    """
    B, C, D = x.shape
    h, hk, hd = cfg.n_heads_padded, cfg.n_kv_heads, cfg.hd
    S = k_cache.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q)
        k_new = rms_norm(p["k_norm"], k_new)
    if cfg.pos_embed == "rope":
        pos = start + jnp.arange(C)[None, :]
        sin, cos = rotary(pos, hd, cfg.rope_theta)
        q = apply_rope(q, sin, cos)
        k_new = apply_rope(k_new, sin, cos)
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k_new.astype(k_cache.dtype), (0, start, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v_new.astype(v_cache.dtype), (0, start, 0, 0))
    n_rep = h // hk
    k_r = _repeat_kv(k_cache, n_rep)
    v_r = _repeat_kv(v_cache, n_rep)
    scale = hd ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_r).astype(jnp.float32) * scale
    q_pos = start + jnp.arange(C)
    mask = jnp.arange(S)[None, :] <= q_pos[:, None]
    scores = jnp.where(mask[None, None], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v_r)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), k_cache, v_cache


# --------------------------------------------------------------------------
# paged KV: block-table gather / scatter
# --------------------------------------------------------------------------
def gather_block_table(pool: jax.Array, table: jax.Array) -> jax.Array:
    """Gather one slot's pages into a contiguous batch-1 cache.

    pool: [L, P, page_tokens, Hkv, hd]; table: [n_blk] int32 physical page
    ids.  Returns [L, 1, n_blk*page_tokens, Hkv, hd], the decode-state
    layout ``prefill_extend`` takes.
    """
    L, P, pt, Hk, hd = pool.shape
    g = pool[:, table]  # [L, n_blk, pt, Hk, hd]
    return g.reshape(L, 1, table.shape[0] * pt, Hk, hd)


def scatter_block_rows(pool: jax.Array, table: jax.Array, rows: jax.Array,
                       start: jax.Array) -> jax.Array:
    """Write ``rows`` [L, n, Hkv, hd] at logical positions start..start+n-1.

    Positions are clamped exactly the way ``dynamic_update_slice`` clamps a
    contiguous cache write (overshoot past max_seq lands in the final page),
    so a request finishing at the KV cap writes where ``decode_step`` would.
    """
    L, P, pt, Hk, hd = pool.shape
    n_blk = table.shape[0]
    n = rows.shape[1]
    S = n_blk * pt

    def body(t, pool):
        pos = jnp.minimum(start + t, S - 1)
        page = table[jnp.minimum(pos // pt, n_blk - 1)]
        off = pos % pt
        row = jax.lax.dynamic_slice(rows, (0, t, 0, 0), (L, 1, Hk, hd))
        return jax.lax.dynamic_update_slice(
            pool, row[:, None].astype(pool.dtype), (0, page, off, 0, 0))

    return jax.lax.fori_loop(0, n, body, pool)
