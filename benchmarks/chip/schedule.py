"""Traffic generator: one mix file in, one seeded request schedule out.

Every seed gets the same work.  A schedule is a run of blocks.  Each block
holds the same list of (system prompt, prompt length, output length) tuples
and the same multiset of arrival gaps, in an order drawn once from a fixed
generator: blocks differ from each other, and every seed sends the same
requests at the same times.  The seed draws the token ids (and the weights),
nothing else.  A seed that also permuted the order inside a block moved the
90th percentile of time to first token by 40% of its median at four fifths
of the knee, far more than two runs of one seed differ; see PERF.md.

A mix file (``traffic/<mix>.json``) holds:

- ``kind``: ``open`` (arrivals on a schedule) or ``closed`` (every request
  queued at time 0);
- ``block``: requests per block;
- ``prompt`` / ``output``: ``{"lengths": [...], "shares": [...]}``; each
  share times ``block`` over the shares' sum must be a whole number;
- ``prefix`` (optional): ``{"length": n, "shares": [...]}``: one system
  prompt of ``n`` tokens per share, put in front of the prompt;
- open mixes: ``rate_rps``; ``lead_in_s``, the last seconds of one block
  sent before the window in an order fixed for every seed, so the window
  starts on a loaded engine (part of set-up); ``tail_s``, arrivals kept up
  after the window while its requests drain.  The window holds a whole
  number of blocks, the nearest to the run's seconds;
- closed mixes: ``requests``, a multiple of ``block``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

import numpy as np

# Pairing of prompt and output lengths inside a block: fixed, never seeded.
_PAIRING_SEED = 20240615


@dataclasses.dataclass
class Item:
    uid: int
    due: float                 # seconds from the schedule's time zero
    prompt: np.ndarray         # [S] int32
    max_new: int
    block: int
    in_window: bool


@dataclasses.dataclass
class Schedule:
    kind: str
    items: List[Item]
    window: Tuple[float, float]     # due-time range of the measured requests
    prefixes: np.ndarray            # [n_prefix, prefix_len] int32; may be empty

    @property
    def window_items(self) -> List[Item]:
        return [it for it in self.items if it.in_window]


def seed_words(seed: int) -> List[int]:
    """A seed of any size as 32-bit words for numpy's SeedSequence."""
    s = int(seed) % (1 << 64)
    return [s & 0xFFFFFFFF, s >> 32]


def _expand(spec: Dict, block: int, what: str) -> List[int]:
    lengths, shares = spec["lengths"], spec["shares"]
    total = sum(shares)
    out: List[int] = []
    for n, sh in zip(lengths, shares):
        k = sh * block / total
        if abs(k - round(k)) > 1e-9:
            raise ValueError(f"{what}: share {sh} of {total} is not a whole "
                             f"number of requests in a block of {block}")
        out += [int(n)] * int(round(k))
    return out


def block_tuples(mix: Dict) -> List[Tuple[int, int, int]]:
    """The (prefix index, prompt length, output length) list of one block.

    The same for every seed and every block.  Prompt lengths stay in order;
    outputs and prefixes are paired with them by one fixed permutation each.
    """
    block = int(mix["block"])
    prompts = _expand(mix["prompt"], block, "prompt")
    outputs = _expand(mix["output"], block, "output")
    fixed = np.random.default_rng(_PAIRING_SEED)
    outputs = [outputs[i] for i in fixed.permutation(block)]
    if "prefix" in mix:
        pre = mix["prefix"]
        ids = _expand({"lengths": list(range(len(pre["shares"]))),
                       "shares": pre["shares"]}, block, "prefix")
        ids = [ids[i] for i in fixed.permutation(block)]
    else:
        ids = [-1] * block
    return list(zip(ids, prompts, outputs))


def block_gaps(block: int, duration: float) -> np.ndarray:
    """Exponential quantiles, scaled to fill one block: Poisson-like gaps
    with a fixed multiset."""
    q = (np.arange(block) + 0.5) / block
    g = -np.log1p(-q)
    return g * (duration / g.sum())


def prefix_len(mix: Dict) -> int:
    return int(mix["prefix"]["length"]) if "prefix" in mix else 0


def build(mix: Dict, vocab: int, seed: int, seconds: float,
          rate: Optional[float] = None) -> Schedule:
    """The seeded schedule of one run (``rate`` overrides the mix's)."""
    block = int(mix["block"])
    tuples = block_tuples(mix)
    rng = np.random.default_rng(seed_words(seed) + [0x7A11])
    pl = prefix_len(mix)
    n_pre = len(mix["prefix"]["shares"]) if "prefix" in mix else 0
    prefixes = rng.integers(0, vocab, (n_pre, pl), dtype=np.int32)

    if mix["kind"] == "closed":
        n_blocks = int(mix["requests"]) // block
        if n_blocks * block != int(mix["requests"]):
            raise ValueError("closed mix: requests must fill whole blocks")
        lead, tail, dur, win = 0.0, 0, 0.0, n_blocks
    elif mix["kind"] == "open":
        r = float(rate if rate is not None else mix["rate_rps"])
        dur = block / r
        lead = min(float(mix.get("lead_in_s", 0.0)), dur)
        win = max(1, int(round(seconds / dur)))
        tail = math.ceil(float(mix.get("tail_s", 0.0)) / dur)
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")

    gaps = block_gaps(block, dur) if dur else np.zeros(block)
    items: List[Item] = []

    def add(due: float, tup, b: int) -> None:
        pre_i, n, out = tup
        body = rng.integers(0, vocab, n, dtype=np.int32)
        prompt = (np.concatenate([prefixes[pre_i], body])
                  if pre_i >= 0 else body)
        items.append(Item(uid=len(items), due=due, prompt=prompt,
                          max_new=int(out), block=b,
                          in_window=0 <= b < win))

    if lead:
        # the last lead_in_s of one block, in an order fixed for every seed:
        # the window starts on a loaded engine, and every seed's is the same
        fixed = np.random.default_rng(_PAIRING_SEED + 1)
        order, g = fixed.permutation(block), gaps[fixed.permutation(block)]
        offs = np.concatenate([[0.0], np.cumsum(g)[:-1]])
        for j in range(block):
            if offs[j] >= dur - lead:
                add(offs[j] - (dur - lead), tuples[order[j]], -1)
    fixed = np.random.default_rng(_PAIRING_SEED + 2)
    for b in range(win + tail):
        order = fixed.permutation(block)
        g = gaps[fixed.permutation(block)]
        offs = np.concatenate([[0.0], np.cumsum(g)[:-1]])
        for j in range(block):
            add(lead + b * dur + float(offs[j]), tuples[order[j]], b)
    return Schedule(kind=mix["kind"], items=items,
                    window=(lead, lead + win * dur), prefixes=prefixes)


def warm_items(mix: Dict, sched: Schedule, vocab: int,
               tokens_per_launch: int, salt: int = 0) -> List[Item]:
    """Set-up requests that reach every prefill, extend and decode shape the
    mix uses.

    One request per prompt length of the mix (and per system prompt, where
    there are more of those), all queued at once so that they share decode
    launches.  Each decodes two launches' worth of tokens.  Token ids come
    from a fixed generator; the system prompts are the run's own, so their
    pages are cached as they would be in a server that has been up a while.
    """
    rng = np.random.default_rng([0x3A3A, salt])
    lengths = sorted(set(mix["prompt"]["lengths"]))
    n_pre = len(sched.prefixes)
    out: List[Item] = []
    for k in range(max(len(lengths), n_pre, 2)):
        body = rng.integers(0, vocab, lengths[k % len(lengths)],
                            dtype=np.int32)
        prompt = (np.concatenate([sched.prefixes[k % n_pre], body])
                  if n_pre else body)
        out.append(Item(uid=-1 - k - 1000 * salt, due=0.0, prompt=prompt,
                        max_new=2 * tokens_per_launch + 1, block=-1,
                        in_window=False))
    return out
