"""Profiler capture and its reduction to device busy, idle and program time.

A traced run records a slice of its window with ``jax.profiler``.  The
reduction reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` into a
plain event list, and everything after that works on the list, so a test can
feed it a small recorded trace:

- device events: the TPU planes' ``XLA Modules`` line (one event per
  program launch) and ``XLA Ops`` line (the operations inside);
- host events: the benchmark's own annotations, ``bench.*`` around the
  loop's steps, waits and submits, and ``engine.*`` around each call into
  the KV manager.

Program and op names are made stable: XLA's numeric hashes and ``.N``
suffixes are stripped, so a name survives an edit of the program.
"""
from __future__ import annotations

import bisect
import glob
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PREFIXES = ("bench.", "engine.")
_HASH = re.compile(r"[(_.]?\d{6,}[)_]?")
_SUFFIX = re.compile(r"\.\d+")
_HLO = re.compile(r"%([^\s=]+)")


def stable(name: str) -> str:
    """``jit_decode_paged(8769873378064142202)`` -> ``jit_decode_paged``;
    ``%fusion.171 = bf16[8,4096]{...} fusion(...)`` -> ``fusion``."""
    name = _HLO.match(name).group(1) if name.startswith("%") else name
    return _SUFFIX.sub("", _HASH.sub("", name)).strip("_")


def self_times(ops: Sequence) -> List[float]:
    """Each op's own time: its duration less the ops nested inside it (the
    TPU trace puts a ``while`` and the ops of its body on one line)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][2], -ops[i][3]))
    own = [float(o[3] - o[2]) for o in ops]
    stack: List[int] = []
    for i in order:
        while stack and ops[stack[-1]][3] <= ops[i][2]:
            stack.pop()
        if stack:
            own[stack[-1]] -= ops[i][3] - ops[i][2]
        stack.append(i)
    return own


def start(path: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(path, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def load(path: str) -> Dict[str, List]:
    """The events of the trace under ``path``: ``modules`` and ``ops`` as
    ``[plane, name, start_ns, end_ns]`` and ``host`` as
    ``[name, start_ns, end_ns, stats]``.  The TPU trace names an op by its
    HLO text and carries no named scope, so ops are told apart by program
    and HLO op name."""
    from jax.profiler import ProfileData
    files = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
    if not files:
        raise RuntimeError(f"no .xplane.pb under {path}")
    pd = ProfileData.from_file(files[0])
    out: Dict[str, List] = {"modules": [], "ops": [], "host": []}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    out[key].append([plane.name, ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        end = ev.start_ns + ev.duration_ns
                        out["host"].append([ev.name, ev.start_ns, end,
                                            dict(ev.stats)])
    return out


def _union(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
           ) -> List[Tuple[float, float]]:
    segs = sorted((max(a, lo), min(b, hi)) for a, b in intervals
                  if b > lo and a < hi)
    merged: List[Tuple[float, float]] = []
    for a, b in segs:
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return merged


def _length(segs: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in segs)


def _overlap(segs: Sequence[Tuple[float, float]], lo: float, hi: float
             ) -> float:
    """Length of sorted, disjoint ``segs`` inside [lo, hi)."""
    i = max(0, bisect.bisect_right([s[0] for s in segs], lo) - 1)
    total = 0.0
    while i < len(segs) and segs[i][0] < hi:
        total += max(0.0, min(segs[i][1], hi) - max(segs[i][0], lo))
        i += 1
    return total


def _host_at(host: Sequence, t: float) -> str:
    """The innermost benchmark annotation open at ``t``."""
    best: Optional[Sequence] = None
    for ev in host:
        if ev[1] <= t < ev[2] and (best is None or ev[1] >= best[1]):
            best = ev
    return best[0] if best is not None else "outside_loop"


def reduce(ev: Dict[str, List], steps: Dict[int, Dict]) -> Optional[Dict]:
    """Reduce a trace over the benchmark steps it holds.

    ``steps`` maps a step index to what the host recorded for it (``active``
    slots, the live lengths of its ``decode`` launch or None).  Device
    programs are given to the step in whose interval (its start to the next
    step's start) they begin.  Returns None when the trace holds no device
    event or fewer than two steps that end in a decode.
    """
    host = ev["host"]
    marks = sorted((h[1], h[3].get("i"), h[2]) for h in host
                   if h[0] == "bench.step" and h[3].get("i") in steps)
    marks = [(k, a, b) for a, k, b in marks]
    # A decode launch ends its step with a host sync, so nothing is in
    # flight after it.  The slice runs from the step after the first such
    # step to the last such step: every launch dispatched inside it also
    # ran inside it.
    synced = [i for i, m in enumerate(marks)
              if steps[m[0]].get("decode") is not None]
    if len(synced) < 2 or not ev["modules"]:
        return None
    marks = marks[synced[0] + 1:synced[-1] + 1]
    planes = sorted({m[0] for m in ev["modules"]})
    lo, hi = marks[0][1], marks[-1][2]
    window_ns = hi - lo
    starts = [m[1] for m in marks]

    per_step: Dict[int, Dict[str, List[float]]] = {}
    programs: Dict[str, float] = {}
    for plane, name, a, b in ev["modules"]:
        if not lo <= a < hi:
            continue
        prog = stable(name)
        k = marks[bisect.bisect_right(starts, a) - 1][0]
        slot = per_step.setdefault(k, {}).setdefault(prog, [0, 0.0])
        slot[0] += 1
        slot[1] += (min(b, hi) - a) / len(planes)
        programs[prog] = programs.get(prog, 0.0) + (min(b, hi) - a) / len(
            planes)

    ops = ev["ops"] or ev["modules"]
    mods = sorted((m[2], m[3], m[1]) for m in ev["modules"])
    mstarts = [m[0] for m in mods]
    op_time: Dict[str, float] = {}
    busy_ns = 0.0
    idle_active_ns = 0.0
    active_ns = 0.0
    gaps: List[Tuple[float, float]] = []
    for plane in planes:
        mine = [(o[2], o[3]) for o in ops if o[0] == plane]
        busy = _union(mine, lo, hi)
        busy_ns += _length(busy)
        prev = lo
        for a, b in busy + [(hi, hi)]:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        for i, (k, a, _) in enumerate(marks):
            if steps[k].get("active", 0) <= 0:
                continue
            b = marks[i + 1][1] if i + 1 < len(marks) else hi
            active_ns += b - a
            idle_active_ns += (b - a) - _overlap(busy, a, b)
    inside = [o for o in ev["ops"] if lo <= o[2] < hi]
    for o, own in zip(inside, self_times(inside)):
        j = bisect.bisect_right(mstarts, o[2]) - 1
        prog = mods[j][2] if j >= 0 and mods[j][1] > o[2] else ""
        lab = f"{stable(prog)}:{stable(o[1])}" if prog else stable(o[1])
        op_time[lab] = op_time.get(lab, 0.0) + own / len(planes)

    n = len(planes)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns / n * 1e-9,
        "active_s": active_ns / n * 1e-9,
        "idle_active_s": idle_active_ns / n * 1e-9,
        "programs_s": {k: v * 1e-9 for k, v in programs.items()},
        "per_step": {k: {p: (c, s * 1e-9) for p, (c, s) in d.items()}
                     for k, d in per_step.items()},
        "steps": [m[0] for m in marks],
        "device_ops": sorted(([k, v * 1e-9] for k, v in op_time.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": [[_host_at(host, (a + b) / 2), (b - a) * 1e-9]
                      for a, b in gaps[:10]],
    }
