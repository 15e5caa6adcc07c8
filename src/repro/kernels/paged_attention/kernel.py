"""Paged decode attention Pallas TPU kernel.

One query row per slot (every query head) attends that slot's first ``n``
cache positions where they lie in the page pool: the pool stays in HBM,
the block table, ``n`` and the layer index are prefetched scalars, and the
kernel copies only the ``ceil(n / page_tokens)`` live pages into VMEM,
double-buffered, ``pages_per_block`` pages a step.  Blocks past the live
pages start no DMA.  The softmax runs online in float32 and the kernel
returns it unnormalized — the accumulated output and the running max and
sum — so the caller can merge another part of the sequence with it.

Grid = (slots,).  A page holds ``page_tokens`` rows of ``[Hkv, hd]``; the
pool is viewed as ``[L, P, page_tokens * Hkv, hd]`` (a bitcast of the
row-major pool), so a block of pages is one ``[R, hd]`` panel whose row
``r`` is token ``r // Hkv`` of the block, KV head ``r % Hkv``.  Every query
head scores the whole panel on the MXU, and the columns of other KV heads
are masked out of its softmax: one algorithm for MHA, GQA and MQA, whose
head grouping (``n_rep = Hq // Hkv``) comes from the shapes alone.  The
masked columns cost MXU work ``Hkv`` times the live work, which the MXU
does well inside the time the page copies take.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention_kernel", "paged_attention_pallas", "NEG_INF"]

NEG_INF = float(jnp.finfo(jnp.float32).min)
# rows of one block's [R, hd] panel the kernel aims for: about a mebibyte
# of K and V copied a step at hd 128 in bf16
BLOCK_ROWS = 2048


def pages_per_block(page_tokens: int, n_kv_heads: int, n_blk: int,
                    block_rows: int = BLOCK_ROWS) -> int:
    """Pages copied a step: enough for a ``block_rows`` panel, at least one
    page and at most the table."""
    return max(1, min(n_blk, block_rows // (page_tokens * n_kv_heads)))


def paged_attention_kernel(tbl_ref, n_ref, layer_ref,             # SMEM
                           q_ref, row_head_ref, col_head_ref, col_tok_ref,
                           k_hbm, v_hbm,                          # HBM
                           acc_ref, m_ref, l_ref,                 # out
                           kbuf, vbuf, sems, *, n_blk: int,
                           page_tokens: int, ppb: int, sm_scale: float):
    """One slot: every query head against the slot's live pages."""
    b = pl.program_id(0)
    rows = kbuf.shape[1] // ppb                 # page_tokens * Hkv
    layer = layer_ref[0]
    n = n_ref[b]
    n_pages = (n + page_tokens - 1) // page_tokens
    n_blocks = (n_pages + ppb - 1) // ppb

    @pl.when(b == 0)
    def _zero():
        # a block's pages past the live ones are never copied: its panel
        # keeps what an earlier block left there, masked out of the
        # softmax, and it must be finite for zero weights to give zeros
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def copies(j, slot):
        """(live, k copy, v copy) of each page of block ``j``."""
        out = []
        for i in range(ppb):
            pos = j * ppb + i
            page = tbl_ref[b * n_blk + jnp.minimum(pos, n_blk - 1)]
            dst = pl.ds(i * rows, rows)
            out.append((pos < n_pages,
                        pltpu.make_async_copy(k_hbm.at[layer, page],
                                              kbuf.at[slot, dst],
                                              sems.at[0, slot]),
                        pltpu.make_async_copy(v_hbm.at[layer, page],
                                              vbuf.at[slot, dst],
                                              sems.at[1, slot])))
        return out

    def start(j, slot):
        for live, ck, cv in copies(j, slot):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(j, slot):
        for live, ck, cv in copies(j, slot):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    @pl.when(n_blocks > 0)
    def _first():
        start(0, 0)

    q = q_ref[...]                                          # [Hq, hd]
    # the operands' own precision, whatever the caller's default: Mosaic
    # refuses a float32 contraction of bfloat16 operands
    precision = (jax.lax.Precision.HIGHEST if kbuf.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    same_head = row_head_ref[...] == col_head_ref[...]      # [Hq, R]

    def body(j, carry):
        acc, m, l = carry
        slot = j % 2

        @pl.when(j + 1 < n_blocks)
        def _next():
            start(j + 1, 1 - slot)

        wait(j, slot)
        k = kbuf[slot]                                      # [R, hd]
        v = vbuf[slot]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=precision,
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        live = same_head & (j * ppb * page_tokens + col_tok_ref[...] < n)
        s = jnp.where(live, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc_new = alpha * acc + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    hq, hd = q.shape
    acc0 = jnp.zeros((hq, hd), jnp.float32)
    m0 = jnp.full((hq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((hq, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_blocks, body, (acc0, m0, l0))
    acc_ref[...] = acc
    m_ref[...] = m
    l_ref[...] = l


def paged_attention_pallas(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, tables: jax.Array,
                           n: jax.Array, layer: jax.Array,
                           block_rows: int = BLOCK_ROWS,
                           interpret: bool = False):
    """Attend each slot's first ``n`` positions in place in the pool.

    q: [B, Hq, hd]; k_pool/v_pool: [L, P, page_tokens, Hkv, hd]; tables:
    [B, n_blk] int32 page ids; n: [B] int32, positions to read (at most
    ``n_blk * page_tokens``); layer: [] int32.  Returns (acc [B, Hq, hd],
    m [B, Hq], l [B, Hq]) in float32: ``acc / l`` is the attention output
    and ``m`` the scores' max; a slot with ``n == 0`` gives acc = l = 0 and
    m = ``NEG_INF``.  ``block_rows`` sizes the panel of one step.
    """
    B, hq, hd = q.shape
    L, P, pt, hk, hd_k = k_pool.shape
    assert hd_k == hd and hq % hk == 0 and v_pool.shape == k_pool.shape
    n_blk = tables.shape[1]
    n_rep = hq // hk
    ppb = pages_per_block(pt, hk, n_blk, block_rows)
    rows = pt * hk
    R = ppb * rows
    c = np.arange(R)
    row_head = jnp.asarray((np.arange(hq) // n_rep)[:, None], jnp.int32)
    col_head = jnp.asarray((c % hk)[None, :], jnp.int32)
    col_tok = jnp.asarray((c // hk)[None, :], jnp.int32)
    # [L, P, pt, Hkv, hd] -> [L, P, pt * Hkv, hd]: merges two adjacent
    # dims, no data moves
    k2 = k_pool.reshape(L, P, rows, hd)
    v2 = v_pool.reshape(L, P, rows, hd)

    def const(shape):
        return pl.BlockSpec(shape, lambda b, *_: (0, 0))

    kernel = functools.partial(
        paged_attention_kernel, n_blk=n_blk, page_tokens=pt, ppb=ppb,
        sm_scale=hd ** -0.5)
    per_slot = pl.BlockSpec((None, hq, hd), lambda b, *_: (b, 0, 0))
    per_slot_1 = pl.BlockSpec((None, hq, 1), lambda b, *_: (b, 0, 0))
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[per_slot, const((hq, 1)), const((1, R)),
                      const((1, R)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[per_slot, per_slot_1, per_slot_1],
            scratch_shapes=[pltpu.VMEM((2, R, hd), k_pool.dtype),
                            pltpu.VMEM((2, R, hd), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=[jax.ShapeDtypeStruct((B, hq, hd), jnp.float32),
                   jax.ShapeDtypeStruct((B, hq, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, hq, 1), jnp.float32)],
        # the scratch is zeroed at the first slot, so slots run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables.reshape(-1).astype(jnp.int32), n.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32),
      q, row_head, col_head, col_tok, k2, v2)
    return acc, m[..., 0], l[..., 0]
