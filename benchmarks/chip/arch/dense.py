"""The plug-in of a dense decoder (Llama / DeepSeek-LLM / Qwen3).

Every piece of the harness that depends on a configuration's architecture
comes from one plug-in, ``arch/<arch>.py``, named by the configuration's
``"arch"``.  A plug-in provides:

- ``model_config(cfg)``: the configuration file's ``model`` block (HF key
  names) as the program's ``ModelConfig``;
- ``shapes(model)`` and ``make(model, seed32)``: the weights in the layout
  the serving engine takes, as shapes and made on the device from a seed;
- ``logits(w, model, tokens, rows, quant)``: the float32 reference, with
  ``quant="fp8"`` for the control;
- ``prefill_flops(model, start, size)`` and
  ``decode_flops_bytes(model, lengths, T, note)``: the work each launch
  needs, the yardstick of every roofline share;
- optionally ``note(eng)``: called before each decode launch, its value is
  kept with that launch and handed to ``decode_flops_bytes`` as ``note``,
  for work that depends on the data.  A dense decoder's work depends on the
  live lengths alone, so this plug-in has none.

Weights: one jitted call makes every leaf, in bf16, in the parameter layout
that the serving engine takes (``emb``, stacked ``layers``,
``final_norm``).  The engine and the float32 reference both read these
weights; neither makes its own.  Matrices are normal with a 1/sqrt(fan-in)
scale; norm scales are small and nonzero (the norm multiplies by
``1 + scale``), so the check covers them too.

Counts: what the mathematics of a dense decoder needs, not what an
implementation does: attention over the positions that are live, logits
only where a token is sampled, each weight read once per forward step.
They never look at a compiled program.  A multiply-add counts as 2 FLOPs.
Weights and KV are bf16 (2 bytes).
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

import jax
import jax.numpy as jnp

from reference import dense as reference

BYTES = 2
DTYPE = jnp.bfloat16
NORM_STD = 0.1

logits = reference.logits


def model_config(cfg: Dict):
    from repro.configs.base import ModelConfig
    m = cfg["model"]
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=int(m["num_hidden_layers"]), d_model=int(m["hidden_size"]),
        n_heads=int(m["num_attention_heads"]),
        n_kv_heads=int(m["num_key_value_heads"]),
        d_ff=int(m["intermediate_size"]), vocab_size=int(m["vocab_size"]),
        head_dim=int(m.get("head_dim") or 0), qk_norm=bool(m.get("qk_norm")),
        rope_theta=float(m["rope_theta"]), act="silu",
        param_dtype="bfloat16", tie_embeddings=False)


# -- weights ------------------------------------------------------------------
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


# -- work counts --------------------------------------------------------------
def dims(model: Dict) -> Dict[str, int]:
    d = int(model["hidden_size"])
    h = int(model["num_attention_heads"])
    return {
        "L": int(model["num_hidden_layers"]),
        "d": d,
        "h": h,
        "hk": int(model["num_key_value_heads"]),
        "hd": int(model.get("head_dim") or d // h),
        "f": int(model["intermediate_size"]),
        "V": int(model["vocab_size"]),
    }


def layer_params(m: Dict) -> int:
    """Matmul weights of one layer (norm scales are left out: they are
    elementwise and a few kilobytes)."""
    x = dims(m)
    attn = x["d"] * x["hd"] * (2 * x["h"] + 2 * x["hk"])
    return attn + 3 * x["d"] * x["f"]


def stack_params(m: Dict) -> int:
    return dims(m)["L"] * layer_params(m)


def weight_bytes(m: Dict) -> int:
    """Weights one forward step reads: every layer and the output head (the
    embedding table is read a row per token, counted with the rows)."""
    x = dims(m)
    return BYTES * (stack_params(m) + x["d"] * x["V"])


def kv_bytes_per_token(m: Dict) -> int:
    x = dims(m)
    return BYTES * x["L"] * 2 * x["hk"] * x["hd"]


def _attn_flops(m: Dict, keys: int) -> int:
    """QK^T and PV of one query over ``keys`` positions, all layers."""
    x = dims(m)
    return 4 * x["L"] * x["h"] * x["hd"] * keys


def prefill_flops(m: Dict, start: int, size: int) -> int:
    """One prefill or extend launch: ``size`` prompt positions from
    ``start``, each attending causally to every earlier position and itself,
    and the logits of the last position only."""
    x = dims(m)
    keys = size * start + size * (size + 1) // 2
    return (2 * stack_params(m) * size + _attn_flops(m, keys)
            + 2 * x["d"] * x["V"])


def decode_flops(m: Dict, lengths: Iterable[int], steps: int) -> int:
    """One decode launch of ``steps`` tokens for slots whose caches hold
    ``lengths`` positions when it starts."""
    x = dims(m)
    per_tok = 2 * stack_params(m) + 2 * x["d"] * x["V"]
    total = 0
    for ln in lengths:
        for t in range(steps):
            total += per_tok + _attn_flops(m, ln + t + 1)
    return total


def decode_bytes(m: Dict, lengths: Iterable[int], steps: int) -> int:
    """HBM bytes one decode launch needs: the weights once per step, each
    active slot's KV at its live length, the new KV rows, and one embedding
    row per token."""
    lengths = list(lengths)
    x = dims(m)
    kv = kv_bytes_per_token(m)
    total = 0
    for t in range(steps):
        total += weight_bytes(m)
        for ln in lengths:
            total += (ln + t) * kv + kv + BYTES * x["d"]
    return total


def decode_flops_bytes(m: Dict, lengths: Iterable[int], steps: int,
                       note: Any = None) -> Tuple[int, int]:
    """(FLOPs, bytes) of one decode launch; ``note`` is unused (this
    plug-in notes nothing)."""
    lengths = list(lengths)
    return decode_flops(m, lengths, steps), decode_bytes(m, lengths, steps)
