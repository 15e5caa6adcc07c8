"""What decides ``correct``: served tokens against the float32 reference.

After the window has closed and the engine is freed, a sample of the
requests it finished, drawn from the seed and always holding the longest,
is run through the reference once each: prompt and served tokens as one
sequence, through the float32 forward that the configuration's plug-in
names (``arch/<arch>.py``'s ``logits``).  At the position that produced
each served token, the number compared is how far the served token's
reference logit lies below the reference's best logit there.  Greedy
decoding in bf16 picks the best token or one within bf16's rounding of it;
a wrong cache row, a lost page or a token altered on its way out lands far
below.

The control puts the reference in the program's place at the precision
step below bf16 (fp8 weights): at the same positions, the token it puts
first stands in for the served token, and its gap is the number compared,
through the same limit.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

ROW_PAD = 128


def sample(finished: Sequence, seed_rng: np.random.Generator,
           min_tokens: int, max_requests: int) -> List:
    """The longest finished request, then others in seeded order until the
    sample serves ``min_tokens`` tokens or holds ``max_requests``."""
    if not finished:
        return []
    by_len = sorted(finished, key=lambda r: (len(r.prompt) + len(r.tokens),
                                             r.uid))
    out = [by_len[-1]]
    rest = by_len[:-1]
    for i in seed_rng.permutation(len(rest)):
        if (sum(len(r.tokens) for r in out) >= min_tokens
                or len(out) >= max_requests):
            break
        out.append(rest[i])
    return out


def _ref_logits(logits, weights, model, seq, rows, quant=None
                ) -> np.ndarray:
    n = len(rows)
    padded = np.full(-(-n // ROW_PAD) * ROW_PAD, rows[-1], np.int32)
    padded[:n] = rows
    out = logits(weights, model, seq, padded, quant=quant)
    return np.asarray(out)[:n]


def gaps(logits: Callable, weights: Dict, model: Dict, prompt: np.ndarray,
         served: Sequence[int], control: bool = False) -> Dict[str, float]:
    """Widest gap below the reference ``logits``' best over one request's
    served tokens (and over the control's picks, where asked)."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    rows = np.arange(len(prompt) - 1, len(seq), dtype=np.int32)
    ref = _ref_logits(logits, weights, model, seq, rows)
    best = ref.max(axis=1)
    out = {"gap": float(np.max(best - ref[np.arange(len(rows)), served]))}
    if control:
        low = _ref_logits(logits, weights, model, seq, rows, quant="fp8")
        pick = low.argmax(axis=1)
        out["control_gap"] = float(np.max(best - ref[np.arange(len(rows)),
                                                     pick]))
    return out


def compare(logits: Callable, weights: Dict, model: Dict,
            requests: Sequence, control: bool = False
            ) -> Dict[str, Optional[float]]:
    """Widest gap below the reference ``logits`` over a sample of requests
    (each with ``prompt`` and ``tokens``).  With ``control`` the fp8
    control's picks replace the served tokens in ``max_logit_gap``; the
    served tokens' own gap is kept as ``program_max_logit_gap``."""
    g: List[float] = []
    c: List[float] = []
    n_tok = 0
    for r in requests:
        one = gaps(logits, weights, model, r.prompt, r.tokens,
                   control=control)
        g.append(one["gap"])
        if control:
            c.append(one["control_gap"])
        n_tok += len(r.tokens)
    served = max(g) if g else None
    return {"max_logit_gap": (max(c) if c else None) if control else served,
            "program_max_logit_gap": served,
            "checked_requests": len(requests), "checked_tokens": n_tok}
