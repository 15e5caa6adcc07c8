"""Mamba2 SSD (state-space duality) Pallas TPU kernel.

Chunked SSD maps onto the TPU as: intra-chunk quadratic term = MXU panels
([Q,N]×[N,Q] and [Q,Q]×[Q,P] matmuls), inter-chunk recurrence = a small
[H_blk, P, N] fp32 state carried in VMEM **scratch across grid steps**.
The grid is (B, H/H_blk, S/Q) with the chunk dimension innermost: Pallas
TPU grids execute sequentially, so the scratch state persists from chunk j
to j+1 and is reset at j == 0 — the TPU-idiomatic replacement for the GPU
version's inter-block shared-memory handoff.

VMEM per step ≈ Q·H_blk·P (x) + 2·Q·N (B,C) + H_blk·Q² (decay) + H_blk·P·N
(state) floats; Q=128..256, H_blk=4..8, P=64, N≤128 keeps this well under
the 16 MiB budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan_kernel", "ssd_scan_pallas"]


def ssd_scan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref,
                    *, chunk: int):
    """One (batch, head-block, chunk) grid cell."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    xt = x_ref[...].astype(jnp.float32)           # [Hb, Q, P]
    dtt = dt_ref[...].astype(jnp.float32)         # [Hb, Q]
    A = a_ref[...].astype(jnp.float32)            # [Hb, 1]
    Bm = b_ref[...].astype(jnp.float32)           # [Q, N]
    Cm = c_ref[...].astype(jnp.float32)           # [Q, N]
    h = state_ref[...]                            # [Hb, P, N] fp32

    Hb, Q, P = xt.shape

    dA = dtt * A                                  # [Hb, Q]  (<= 0)
    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    # inclusive prefix sum over the chunk as one MXU matmul with an upper
    # triangle of ones (Mosaic has no cumsum lowering)
    cum = jax.lax.dot_general(
        dA, (qi <= ki).astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)       # [Hb, Q]
    tot = jnp.sum(dA, axis=1, keepdims=True)      # [Hb, 1]

    # ---- intra-chunk quadratic term ----
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, Q]
    diff = cum[:, :, None] - cum[:, None, :]      # [Hb, Q, Q]
    L = jnp.where((qi >= ki)[None], jnp.exp(diff), 0.0)
    G = CB[None] * L * dtt[:, None, :]            # [Hb, Qq, Qk]
    y_intra = jax.lax.dot_general(
        G, xt, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)       # [Hb, Q, P]

    # ---- inter-chunk term (read carried state) ----
    # y_inter[h,q,p] = decay_q[h,q] * sum_n C[q,n] h[h,p,n]
    Ch = jax.lax.dot_general(
        jnp.broadcast_to(Cm[None], (Hb, Q, Cm.shape[1])), h,
        (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)       # [Hb, Q, P]
    y_inter = Ch * jnp.exp(cum)[:, :, None]

    y_ref[...] = (y_intra + y_inter).astype(y_ref.dtype)

    # ---- state update ----
    w = dtt * jnp.exp(tot - cum)                  # [Hb, Q]
    xw = xt * w[:, :, None]                       # [Hb, Q, P]
    dstate = jax.lax.dot_general(
        xw, jnp.broadcast_to(Bm[None], (Hb, Q, Bm.shape[1])),
        (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)       # [Hb, P, N]
    state_ref[...] = h * jnp.exp(tot)[:, :, None] + dstate


def ssd_scan_pallas(xh: jax.Array, dt: jax.Array, A: jax.Array,
                    Bc: jax.Array, Cc: jax.Array, chunk: int = 128,
                    head_block: int = 0, interpret: bool = False
                    ) -> jax.Array:
    """xh: [B,S,H,P]; dt: [B,S,H] (post-softplus); A: [H] (negative);
    Bc/Cc: [B,S,N].  Returns y: [B,S,H,P]."""
    B, S, H, P = xh.shape
    N = Bc.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    if head_block <= 0:
        head_block = next(h for h in (8, 4, 2, 1) if H % h == 0)
    grid = (B, H // head_block, S // chunk)

    kernel = functools.partial(ssd_scan_kernel, chunk=chunk)
    # head-major layouts: every block's last two dims are (rows, lanes) the
    # TPU tiles — (chunk, P), (head_block, chunk), (head_block, 1) — instead
    # of a head_block-wide lane dim the compiler refuses
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, head_block, chunk, P),
                         lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((None, head_block, chunk), lambda b, h, j: (b, h, j)),
            pl.BlockSpec((head_block, 1), lambda b, h, j: (h, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, h, j: (b, j, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, h, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, head_block, chunk, P),
                               lambda b, h, j: (b, h, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), xh.dtype),
        scratch_shapes=[pltpu.VMEM((head_block, P, N), jnp.float32)],
        interpret=interpret,
    )(xh.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1), A.reshape(H, 1),
      Bc, Cc)
    return y.transpose(0, 2, 1, 3)
