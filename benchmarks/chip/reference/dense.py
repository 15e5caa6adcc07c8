"""Plain float32 forward of a dense decoder (Llama / DeepSeek-LLM / Qwen3).

Written from the published description, independent of the code under
test: pre-norm blocks of RMSNorm, grouped-query attention with rotary
position embedding (half-split rotation, as HF's ``rotate_half``), optional
per-head RMSNorm of queries and keys (Qwen3), and a SwiGLU MLP; a final
RMSNorm and an untied output head.  RMSNorm multiplies by ``1 + scale``,
the parametrisation of the weights this benchmark makes.

Everything is float32 at the highest matmul precision.  The weights arrive
in bf16 and are cast one layer at a time inside the layer scan, and
attention runs in blocks of queries, so a 4k-token sequence of a full-width
layer fits beside the bf16 weights.

``quant="fp8"`` is the control: every weight matrix is rounded to
float8_e4m3fn with one scale per tensor before use, the precision step below
the configuration's bf16.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
FP8_MAX = 448.0


def _f32(w, quant, rows=None):
    """A weight in float32 (only ``rows`` of it, where given), rounded to
    fp8 first for the control."""
    x = (w if rows is None else w[rows]).astype(jnp.float32)
    if quant == "fp8":
        s = jnp.max(jnp.abs(w)).astype(jnp.float32) / FP8_MAX
        x = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def _rope(x, pos, theta):
    hd = x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal attention; q [S, H, hd], k/v [S, H, hd]; blocks of queries."""
    S, H, hd = q.shape
    nb = S // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, H, hd)
    kpos = jnp.arange(S)

    def one(args):
        i, qi = args
        s = jnp.einsum("qhd,khd->hqk", qi, k) * hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(one, (jnp.arange(nb), qb))
    return out.reshape(S, H, hd)


@functools.partial(jax.jit, static_argnames=("spec", "quant"))
def _hidden(w, tokens, spec, quant):
    eps, theta, qk_norm = spec
    x = _f32(w["emb"]["embed"], quant, rows=tokens)
    S = tokens.shape[0]
    pos = jnp.arange(S)

    def layer(x, lp):
        a = lp["attn"]
        h = _rms(x, lp["ln1"]["scale"], eps)
        q = jnp.einsum("sd,dhk->shk", h, _f32(a["wq"], quant))
        k = jnp.einsum("sd,dhk->shk", h, _f32(a["wk"], quant))
        v = jnp.einsum("sd,dhk->shk", h, _f32(a["wv"], quant))
        if qk_norm:
            q = _rms(q, a["q_norm"]["scale"], eps)
            k = _rms(k, a["k_norm"]["scale"], eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        rep = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        o = jnp.einsum("shk,hkd->sd", _attention(q, k, v),
                       _f32(a["wo"], quant))
        x = x + o
        m = lp["mlp"]
        h = _rms(x, lp["ln2"]["scale"], eps)
        g = jax.nn.silu(h @ _f32(m["w_gate"], quant)) * (h @ _f32(m["w_up"],
                                                                  quant))
        return x + g @ _f32(m["w_down"], quant), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    return _rms(x, w["final_norm"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("quant",))
def _logits(w, hidden, rows, quant):
    return hidden[rows] @ _f32(w["emb"]["unembed"], quant)


def spec_of(model: Dict):
    return (float(model.get("rms_norm_eps", 1e-6)),
            float(model.get("rope_theta", 10000.0)),
            bool(model.get("qk_norm", False)))


def logits(w: Dict, model: Dict, tokens: np.ndarray, rows: np.ndarray,
           quant: Optional[str] = None) -> jax.Array:
    """float32 logits [len(rows), V] at positions ``rows`` of ``tokens``.

    The sequence is padded at its end to a multiple of 512; causal masking
    keeps the padding out of every row asked for.
    """
    S = len(tokens)
    pad = -(-S // Q_BLOCK) * Q_BLOCK
    toks = np.zeros(pad, np.int32)
    toks[:S] = tokens
    with jax.default_matmul_precision("highest"):
        h = _hidden(w, jnp.asarray(toks), spec_of(model), quant)
        return _logits(w, h, jnp.asarray(rows, jnp.int32), quant)
