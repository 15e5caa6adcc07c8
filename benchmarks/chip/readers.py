"""Shared arithmetic of the metric readers in ``metrics/``.

Every reader takes the run record that ``run.py`` builds and returns a
number, or None where its run holds nothing to read (no trace, no launch of
its program in the slice).  A roofline share is never returned as 0 for
lack of data: it is None then.  The work of each launch comes from the
record's architecture plug-in (``run.arch``, ``arch/<arch>.py``), given the
launch's record: a prefill's start and size; a decode's live lengths,
tokens per launch and what the plug-in noted for it.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional

DECODE = "jit_decode_paged"
PREFILL = "jit_extend_paged"


def p90(values: Iterable[float]) -> Optional[float]:
    """Nearest-rank 90th percentile."""
    xs = sorted(values)
    if not xs:
        return None
    return xs[max(0, math.ceil(0.9 * len(xs)) - 1)]


def ttft_s(r, giveup: float) -> float:
    """Due time to the first token; a request that never gave one counts as
    later than every other."""
    return (r.first if r.first is not None else giveup) - r.due


def tpot_s(r, giveup: float) -> float:
    if r.failed or r.first is None or r.n_tokens < 2:
        return giveup - r.due
    return (r.last - r.first) / (r.n_tokens - 1)


def queue_wait_s(r, giveup: float) -> float:
    return (r.admit if r.admit is not None else giveup) - r.due


def _slice_steps(run) -> List[int]:
    return run.trace["steps"] if run.trace else []


def program_time(run, prog: str):
    """(launches, device seconds) of ``prog`` over the traced slice."""
    n, s = 0, 0.0
    for k in _slice_steps(run):
        c, t = run.trace["per_step"].get(k, {}).get(prog, (0, 0.0))
        n += c
        s += t
    return n, s


def prefill_work(run):
    """(tokens, FLOPs) of the prefill launches of the traced slice."""
    tok = flops = 0
    for k in _slice_steps(run):
        for start, size in run.steps[k]["prefills"]:
            tok += size
            flops += run.arch.prefill_flops(run.model, start, size)
    return tok, flops


def decode_work(run, keys: Iterable[int]):
    """(FLOPs, bytes) of the decode launches of the given steps."""
    flops = nbytes = 0
    for k in keys:
        step = run.steps[k]
        if step["decode"] is not None:
            f, b = run.arch.decode_flops_bytes(run.model, step["decode"],
                                               run.T, step.get("note"))
            flops += f
            nbytes += b
    return flops, nbytes


def decode_step_ms(run) -> Optional[float]:
    n, s = program_time(run, DECODE)
    return 1e3 * s / n if n else None


def decode_hbm_roofline(run) -> Optional[float]:
    n, s = program_time(run, DECODE)
    if not n or s <= 0:
        return None
    _, nbytes = decode_work(run, _slice_steps(run))
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / s


def prefill_ms_per_ktok(run) -> Optional[float]:
    n, s = program_time(run, PREFILL)
    tok, _ = prefill_work(run)
    return 1e6 * s / tok if n and tok else None


def prefill_flop_roofline(run) -> Optional[float]:
    n, s = program_time(run, PREFILL)
    _, flops = prefill_work(run)
    if not n or s <= 0 or not flops:
        return None
    return 100.0 * flops / run.peaks["bf16_flops_per_s"] / s


def step_mfu(run) -> Optional[float]:
    """Model FLOPs of every launch in the slice over busy time at peak."""
    if not run.trace or run.trace["busy_s"] <= 0:
        return None
    steps = _slice_steps(run)
    _, pf = prefill_work(run)
    df, _ = decode_work(run, steps)
    return 100.0 * (pf + df) / (run.trace["busy_s"]
                                * run.peaks["bf16_flops_per_s"])


def window_mfu(run) -> Optional[float]:
    """Model FLOPs of every launch in the window over the window at peak."""
    keys = run.window_steps
    if not keys or run.elapsed <= 0:
        return None
    df, _ = decode_work(run, keys)
    pf = sum(run.arch.prefill_flops(run.model, a, n)
             for k in keys for a, n in run.steps[k]["prefills"])
    return 100.0 * (pf + df) / (run.elapsed * run.peaks["bf16_flops_per_s"])


def device_idle(run) -> Optional[float]:
    if not run.trace or run.trace["active_s"] <= 0:
        return None
    return 100.0 * run.trace["idle_active_s"] / run.trace["active_s"]


def batch_occupancy(run) -> Optional[float]:
    acts = [run.steps[k]["iter_active"] for k in run.window_steps
            if run.steps[k]["iter_active"] is not None]
    if not acts:
        return None
    return 100.0 * sum(acts) / (len(acts) * run.slots)
