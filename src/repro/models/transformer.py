"""Decoder-only transformer LM (dense and MoE) with scan-over-layers.

The layer stack is a single ``lax.scan`` over stacked per-layer parameters —
the command footprint (compiled HLO size) is O(1) in depth, which is the
paper's CUDA-Graph lesson applied to the compile path (see core/graphs.py).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import (attention, decode_attention, decode_attention_paged,
                        extend_attention, init_attention, init_kv_cache)
from .layers import (Params, cross_entropy_loss, dtype_of, embed,
                     init_embedding, init_mlp, init_rms_norm, mlp, rms_norm,
                     unembed)
from .moe import init_moe, moe_block

__all__ = ["TransformerLM"]

MOE_AUX_COEF = 0.01


def init_block(key: jax.Array, cfg: ModelConfig, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": init_rms_norm(cfg.d_model, dtype),
        "attn": init_attention(k1, cfg, dtype),
        "ln2": init_rms_norm(cfg.d_model, dtype),
    }
    if cfg.n_experts:
        p["moe"] = init_moe(k2, cfg, dtype)
    else:
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, dtype)
    return p


def block_forward(p: Params, cfg: ModelConfig, x: jax.Array,
                  positions: Optional[jax.Array], impl: str = "ref"
                  ) -> Tuple[jax.Array, jax.Array]:
    a = attention(p["attn"], cfg, rms_norm(p["ln1"], x), positions, impl=impl)
    x = x + a
    h = rms_norm(p["ln2"], x)
    if cfg.n_experts:
        m, aux = moe_block(p["moe"], cfg, h)
    else:
        m, aux = mlp(p["mlp"], h, cfg.act), jnp.zeros((), jnp.float32)
    return x + m, aux


def _serve_ffn(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """The block's second half on the serving paths (no aux loss)."""
    h = rms_norm(p["ln2"], x)
    if cfg.n_experts:
        m, _ = moe_block(p["moe"], cfg, h)
    else:
        m = mlp(p["mlp"], h, cfg.act)
    return x + m


def block_decode(p: Params, cfg: ModelConfig, x: jax.Array,
                 k_cache: jax.Array, v_cache: jax.Array, length: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    a, k_cache, v_cache = decode_attention(
        p["attn"], cfg, rms_norm(p["ln1"], x), k_cache, v_cache, length)
    return _serve_ffn(p, cfg, x + a), k_cache, v_cache


def block_decode_paged(p: Params, cfg: ModelConfig, x: jax.Array,
                       k_pool: jax.Array, v_pool: jax.Array,
                       tables: jax.Array, layer: jax.Array,
                       rows_k: jax.Array, rows_v: jax.Array,
                       start: jax.Array, pos: jax.Array
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    a, rows_k, rows_v = decode_attention_paged(
        p["attn"], cfg, rms_norm(p["ln1"], x), k_pool, v_pool, tables,
        layer, rows_k, rows_v, start, pos)
    return _serve_ffn(p, cfg, x + a), rows_k, rows_v


def block_extend(p: Params, cfg: ModelConfig, x: jax.Array,
                 k_cache: jax.Array, v_cache: jax.Array, start: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    a, k_cache, v_cache = extend_attention(
        p["attn"], cfg, rms_norm(p["ln1"], x), k_cache, v_cache, start)
    return _serve_ffn(p, cfg, x + a), k_cache, v_cache


class TransformerLM:
    """Dense / MoE decoder-only LM."""

    def __init__(self, cfg: ModelConfig, impl: str = "ref") -> None:
        self.cfg = cfg
        self.impl = impl
        # residual-stream sharding constraint (sequence parallelism); set by
        # the launcher: lambda x: with_sharding_constraint(x, P(dp,'model',None))
        self.constraint = lambda x: x

    # ---- params ----------------------------------------------------------
    def init_params(self, key: jax.Array) -> Params:
        cfg = self.cfg
        dtype = dtype_of(cfg)
        k_emb, k_layers, k_fn = jax.random.split(key, 3)
        layer_keys = jax.random.split(k_layers, cfg.n_layers)
        layers = jax.vmap(lambda k: init_block(k, cfg, dtype))(layer_keys)
        return {
            "emb": init_embedding(k_emb, cfg.vocab_padded, cfg.d_model,
                                  dtype, cfg.tie_embeddings),
            "layers": layers,
            "final_norm": init_rms_norm(cfg.d_model, dtype),
        }

    # ---- forward / loss -------------------------------------------------
    def hidden_states(self, params: Params, tokens: jax.Array,
                      mode: str = "train") -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        x = embed(params["emb"], tokens, cfg.embed_scale)
        positions = jnp.arange(tokens.shape[1])[None, :]

        def scan_fn(carry, lp):
            y, aux = block_forward(lp, cfg, carry, positions, self.impl)
            return self.constraint(y), aux

        if cfg.remat and mode == "train":
            scan_fn = jax.checkpoint(scan_fn)
        x, auxs = jax.lax.scan(scan_fn, self.constraint(x), params["layers"])
        x = rms_norm(params["final_norm"], x)
        return x, jnp.mean(auxs)

    def loss(self, params: Params, batch: Dict[str, jax.Array]
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        x, aux = self.hidden_states(params, batch["tokens"], mode="train")
        ce = cross_entropy_loss(params["emb"], x, batch["labels"],
                                cfg.loss_chunk, vocab_valid=cfg.vocab_size)
        total = ce + (MOE_AUX_COEF * aux if cfg.n_experts else 0.0)
        return total, {"ce": ce, "aux": aux}

    # ---- serving ---------------------------------------------------------
    def init_decode_state(self, batch: int, max_seq: int) -> Params:
        return init_kv_cache(self.cfg, batch, max_seq, dtype_of(self.cfg))

    def prefill(self, params: Params, tokens: jax.Array, max_seq: int
                ) -> Tuple[Params, jax.Array]:
        """Run the prompt, building the KV cache; returns (state, last logits)."""
        cfg = self.cfg
        x = embed(params["emb"], tokens, cfg.embed_scale)
        return self.prefill_embeds(params, x, max_seq)

    def prefill_embeds(self, params: Params, x: jax.Array, max_seq: int
                       ) -> Tuple[Params, jax.Array]:
        """Prefill from precomputed embeddings (used by the VLM frontend)."""
        cfg = self.cfg
        B, S = x.shape[:2]
        positions = jnp.arange(S)[None, :]

        def scan_fn(carry, lp):
            h = rms_norm(lp["ln1"], carry)
            # recompute K/V for the cache (same path as attention())
            k = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wk"])
            v = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wv"])
            if cfg.qk_norm:
                from .layers import rms_norm as _rn
                k = _rn(lp["attn"]["k_norm"], k)
            if cfg.pos_embed == "rope":
                from .layers import rotary, apply_rope
                sin, cos = rotary(positions, cfg.hd, cfg.rope_theta)
                k = apply_rope(k, sin, cos)
            y, _ = block_forward(lp, cfg, carry, positions, self.impl)
            return y, (k, v)

        x, (ks, vs) = jax.lax.scan(scan_fn, x, params["layers"])
        x = rms_norm(params["final_norm"], x)
        logits = unembed(params["emb"], x[:, -1:, :])
        state = self.init_decode_state(B, max_seq)
        state["k"] = jax.lax.dynamic_update_slice(
            state["k"], ks.astype(state["k"].dtype), (0, 0, 0, 0, 0))
        state["v"] = jax.lax.dynamic_update_slice(
            state["v"], vs.astype(state["v"].dtype), (0, 0, 0, 0, 0))
        state["length"] = jnp.asarray(S, jnp.int32)
        return state, logits

    def prefill_extend(self, params: Params, state: Params, tokens: jax.Array
                       ) -> Tuple[Params, jax.Array]:
        """Extend a decode state by one prompt chunk (chunked prefill).

        tokens: [B, C] prompt positions state["length"]..length+C-1.  Returns
        (new state, logits at the chunk's last position).  Chaining chunks is
        bit-identical to a single whole-prompt ``prefill`` (future cache
        positions are zero and masked to exactly-zero attention weight).
        """
        cfg = self.cfg
        x = embed(params["emb"], tokens, cfg.embed_scale)
        start = state["length"]

        def scan_fn(carry, inp):
            lp, kc, vc = inp
            y, kc, vc = block_extend(lp, cfg, carry, kc, vc, start)
            return y, (kc, vc)

        x, (new_k, new_v) = jax.lax.scan(
            scan_fn, x, (params["layers"], state["k"], state["v"]))
        x = rms_norm(params["final_norm"], x)
        logits = unembed(params["emb"], x[:, -1:, :])
        new_state = {"k": new_k, "v": new_v,
                     "length": start + jnp.asarray(tokens.shape[1], jnp.int32)}
        return new_state, logits

    def decode_step(self, params: Params, state: Params, tokens: jax.Array
                    ) -> Tuple[Params, jax.Array]:
        """One token for every sequence. tokens: [B, 1]."""
        cfg = self.cfg
        x = embed(params["emb"], tokens, cfg.embed_scale)
        length = state["length"]

        def scan_fn(carry, inp):
            lp, kc, vc = inp
            y, kc, vc = block_decode(lp, cfg, carry, kc, vc, length)
            return y, (kc, vc)

        x, (new_k, new_v) = jax.lax.scan(
            scan_fn, x, (params["layers"], state["k"], state["v"]))
        x = rms_norm(params["final_norm"], x)
        logits = unembed(params["emb"], x)
        new_state = {"k": new_k, "v": new_v, "length": length + 1}
        return new_state, logits

    def decode_rows_paged(self, params: Params, k_pool: jax.Array,
                          v_pool: jax.Array, tables: jax.Array,
                          lengths: jax.Array, tok: jax.Array, T: int):
        """T greedy tokens of every slot, attending the pages in place.

        k_pool/v_pool: [L, P, page_tokens, Hkv, hd], read only; tables:
        [B, n_blk] page ids; lengths: [B] valid positions; tok: [B, 1], the
        token at position ``lengths``.  The T token steps carry only the
        token and a T-row window per layer, standing for cache positions
        start..start+T-1 with start = min(length, S - T) (at most two
        pages): it starts from the pool's rows there and ends holding what
        T ``decode_step`` calls would leave at those positions, clamped
        final-position writes included, so scattering it back at ``start``
        updates the cache as they would.  The layer scan passes the layer
        index and never scans over the pools, which the paged attention
        kernel reads where they lie.  Returns (toks [B, T], nxt [B, 1],
        rows_k, rows_v [L, B, T, Hkv, hd]).
        """
        cfg = self.cfg
        L, _, pt = k_pool.shape[:3]
        S = tables.shape[1] * pt
        start = jnp.minimum(lengths, S - T)
        at = start[:, None] + jnp.arange(T)[None, :]              # [B, T]
        page = jnp.take_along_axis(tables, at // pt, axis=1)

        def window(pool):
            return pool[:, page, at % pt]                 # [L, B, T, Hk, hd]

        def step(carry, t):
            tok, rk, rv = carry
            x = embed(params["emb"], tok, cfg.embed_scale)

            def layer(x, inp):
                lp, rk_l, rv_l, i = inp
                x, rk_l, rv_l = block_decode_paged(
                    lp, cfg, x, k_pool, v_pool, tables, i, rk_l, rv_l,
                    start, lengths + t)
                return x, (rk_l, rv_l)

            x, (rk, rv) = jax.lax.scan(
                layer, x, (params["layers"], rk, rv, jnp.arange(L)))
            logits = unembed(params["emb"], rms_norm(params["final_norm"], x))
            nxt = jnp.argmax(logits, axis=-1).astype(tok.dtype)
            return (nxt, rk, rv), nxt[:, 0]

        (nxt, rk, rv), toks = jax.lax.scan(
            step, (tok, window(k_pool), window(v_pool)), jnp.arange(T))
        return toks.T, nxt, rk, rv
