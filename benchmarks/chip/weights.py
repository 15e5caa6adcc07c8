"""Random weights of a dense decoder, made on the device from a seed.

One jitted call makes every leaf, in bf16, in the parameter layout that the
serving engine takes (``emb``, stacked ``layers``, ``final_norm``).  The
engine and the float32 reference both read these weights; neither makes
its own.  Matrices are normal with a 1/sqrt(fan-in) scale; norm scales are
small and nonzero (the norm multiplies by ``1 + scale``), so the check
covers them too.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from counts import dims

DTYPE = jnp.bfloat16
NORM_STD = 0.1


def _normal(key, shape, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(DTYPE)


def _make(model: Dict, key: jax.Array) -> Dict:
    x = dims(model)
    L, d, h, hk, hd, f, V = (x[k] for k in ("L", "d", "h", "hk", "hd", "f",
                                            "V"))
    ks = iter(jax.random.split(key, 16))
    attn = {
        "wq": _normal(next(ks), (L, d, h, hd), d ** -0.5),
        "wk": _normal(next(ks), (L, d, hk, hd), d ** -0.5),
        "wv": _normal(next(ks), (L, d, hk, hd), d ** -0.5),
        "wo": _normal(next(ks), (L, h, hd, d), (h * hd) ** -0.5),
    }
    if model.get("qk_norm"):
        attn["q_norm"] = {"scale": _normal(next(ks), (L, hd), NORM_STD)}
        attn["k_norm"] = {"scale": _normal(next(ks), (L, hd), NORM_STD)}
    return {
        "emb": {"embed": _normal(next(ks), (V, d), d ** -0.5),
                "unembed": _normal(next(ks), (d, V), d ** -0.5)},
        "layers": {
            "ln1": {"scale": _normal(next(ks), (L, d), NORM_STD)},
            "attn": attn,
            "ln2": {"scale": _normal(next(ks), (L, d), NORM_STD)},
            "mlp": {"w_gate": _normal(next(ks), (L, d, f), d ** -0.5),
                    "w_up": _normal(next(ks), (L, d, f), d ** -0.5),
                    "w_down": _normal(next(ks), (L, f, d), f ** -0.5)},
        },
        "final_norm": {"scale": _normal(next(ks), (d,), NORM_STD)},
    }


def make(model: Dict, seed32: int) -> Dict:
    """Weights for ``model`` (a configuration's model block) from a 32-bit
    seed, in one compiled call."""
    return jax.jit(lambda k: _make(model, k))(jax.random.PRNGKey(seed32))


def shapes(model: Dict) -> Dict:
    return jax.eval_shape(lambda k: _make(model, k), jax.random.PRNGKey(0))
