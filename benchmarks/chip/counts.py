"""FLOPs and bytes that the work needs, from shapes and live lengths.

These count what the mathematics of a dense decoder needs, not what an
implementation does: attention over the positions that are live, logits only
where a token is sampled, each weight read once per forward step.  They are
the yardstick of every roofline share, so they never look at a compiled
program.

Shapes come from a configuration's ``model`` block (HF key names).  A
multiply-add counts as 2 FLOPs.  Weights and KV are bf16 (2 bytes).
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

BYTES = 2


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


def decode_flops_bytes(m: Dict, lengths: Iterable[int], steps: int
                       ) -> Tuple[int, int]:
    lengths = list(lengths)
    return decode_flops(m, lengths, steps), decode_bytes(m, lengths, steps)
