"""Pure-jnp float32 oracle for the paged decode attention kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import NEG_INF

__all__ = ["paged_attention_ref"]


def paged_attention_ref(q, k_pool, v_pool, tables, n, layer):
    """The kernel's (acc, m, l) from a gather of each slot's whole table.

    q: [B, Hq, hd]; pools [L, P, page_tokens, Hkv, hd]; tables [B, n_blk];
    n [B]; layer [].  Everything in float32.
    """
    B, hq, hd = q.shape
    _, _, pt, hk, _ = k_pool.shape
    S = tables.shape[1] * pt
    k = k_pool[layer][tables].reshape(B, S, hk, hd).astype(jnp.float32)
    v = v_pool[layer][tables].reshape(B, S, hk, hd).astype(jnp.float32)
    qg = q.astype(jnp.float32).reshape(B, hk, hq // hk, hd)
    s = jnp.einsum("bgrd,bsgd->bgrs", qg, k,
                   precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
    live = jnp.arange(S)[None, :] < n[:, None]               # [B, S]
    s = jnp.where(live[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(live[:, None, None, :], jnp.exp(s - m[..., None]), 0.0)
    acc = jnp.einsum("bgrs,bsgd->bgrd", p, v,
                     precision=jax.lax.Precision.HIGHEST)
    return (acc.reshape(B, hq, hd), m.reshape(B, hq),
            jnp.sum(p, axis=-1).reshape(B, hq))
