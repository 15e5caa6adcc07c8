"""Flash attention Pallas TPU kernel.

TPU adaptation of the paper-adjacent flash algorithm: the GPU version tiles
over SRAM with warp-level softmax; on TPU the tiles live in VMEM and the
MXU consumes [block_q, hd] × [hd, block_k] panels.  Grid = (B·H, S/block_q);
the kernel streams KV blocks with a fori_loop carrying the running
(max, sum, acc) in fp32 VREGs, skipping fully-masked future blocks via the
grid index — the causal-skip halves compute vs the masked dense loop.

Block sizes default to (128, 128): the MXU is 128×128 and hd ∈ {64,128,256}
for every assigned arch, so panels are hardware-aligned.  K and V are NOT
streamed over the grid: each grid cell's K and V blocks are the head's whole
sequence, ``(S, hd)``, held in VMEM, and the fori_loop walks it in block_k
slices.  That fits at S=4096, hd=256 (2 MiB per operand in bf16), but at
S=32768 the whole-sequence blocks exceed VMEM and the TPU compiler refuses
the kernel.  Streaming K/V blocks over a grid axis would lift that limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flash_attention_kernel", "flash_attention_pallas"]

NEG_INF = float(jnp.finfo(jnp.float32).min)


def flash_attention_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int,
                           causal: bool, sm_scale: float, seq_len: int):
    """One (batch·head, q-block) grid cell."""
    q_idx = pl.program_id(1)
    block_q = q_ref.shape[0]
    hd = q_ref.shape[1]

    q = q_ref[...].astype(jnp.float32) * sm_scale      # [bq, hd]

    n_k_blocks = seq_len // block_k
    if causal:
        # last kv block that intersects this q block
        last = (q_idx + 1) * block_q // block_k
        n_iter = jnp.minimum(last + ((q_idx + 1) * block_q % block_k != 0),
                             n_k_blocks)
        n_iter = jnp.maximum(n_iter, 1)
    else:
        n_iter = n_k_blocks

    def body(j, carry):
        acc, m, l = carry
        rows = pl.ds(j * block_k, block_k)
        k = k_ref[rows, :].astype(jnp.float32)
        v = v_ref[rows, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((block_q, hd), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_iter, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = True, block_q: int = 128,
                           block_k: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q,k,v: [B, S, H, hd] (H already GQA-expanded) -> [B, S, H, hd]."""
    B, S, H, hd = q.shape
    assert k.shape == v.shape == (B, S, H, hd)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0

    # [B, S, H, hd] -> [B*H, S, hd]: each grid row owns one head's sequence
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)

    grid = (B * H, S // block_q)
    kernel = functools.partial(
        flash_attention_kernel, block_k=block_k, causal=causal,
        sm_scale=hd ** -0.5, seq_len=S)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, S, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, S, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
