#!/usr/bin/env python3
"""The host phases of the engine's steps, read from the program's own spans.

    python3 benchmarks/chip/phases.py --workload <cell> --seeds 11,12 \\
        --seconds 51 [--trace 1]

Each seed is a whole run of the cell (``run.execute``) with one more sink on
the engine's session: :class:`StepSpans` keeps each ``serve.step`` span with
the program spans (``serve.*``) and launches (``dispatch.<name>``) that
closed inside it.  The window is the steps between the run's two reads of
``kv.stats()``, which ``execute`` takes where its window starts and ends.
From the window's steps it reads:

- ``host_ms_per_step``: the median, over steps that launched a decode, of
  ``serve.step`` less the read-back spans inside it: the host's own time in
  a step, without its waits for the device;
- ``stall_ms``: the sum over steps of the time each takes beyond twice the
  median decode step;
- ``stalls``: each step over twice that median, with where it began in the
  window, each phase's time and its excess over that phase's median, and
  the phase that holds most of the step's excess.

With ``--trace 1`` the idle gaps of the breakdown are also named by program
spans: the innermost ``serve.*`` or ``dispatch.*`` span open in the gap.
One JSON line per run, with the run's own metrics and info.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List, Optional

import numpy as np

import run
import xtrace

SPAN_EVENT = "obs.span"
STEP = "serve.step"
READBACKS = ("serve.decode.readback", "serve.prefill.readback")
# The phases a step's time is split into; what is left is "host".
PHASES = ("serve.decode.upload", "serve.decode.readback",
          "serve.prefill.readback")


class StepSpans:
    """Session sink: one record per ``serve.step`` span, with the spans and
    launches that closed inside it, and the step counts at which the
    engine's ``kv.stats()`` was read (``marks``)."""

    def __init__(self) -> None:
        self.steps: List[Dict] = []
        self.marks: List[int] = []
        self._inside: List[List] = []

    def attach(self, eng) -> None:
        """Install on an engine: the sink, and a mark at each read of its
        KV manager's ``stats``."""
        eng.session.add_sink(self)
        stats = eng.kv.stats

        def marked():
            self.marks.append(len(self.steps))
            return stats()
        eng.kv.stats = marked

    def emit(self, ev) -> None:
        path = ev.meta.get("span_path", "")
        if ev.name == SPAN_EVENT and path == STEP:
            self.steps.append({"t0": ev.t - ev.dur_s, "ms": 1e3 * ev.dur_s,
                               "decoded": ev.meta.get("decoded", 0),
                               "spans": self._inside})
            self._inside = []
        elif not path.startswith(STEP + "/"):
            return
        elif ev.name == SPAN_EVENT:
            self._inside.append([ev.meta["span"], 1e3 * ev.dur_s])
        elif ev.kind == "dispatch":
            self._inside.append(["dispatch." + ev.name, 1e3 * ev.dur_s])

    def window(self) -> List[Dict]:
        if len(self.marks) < 2:
            return []
        return self.steps[self.marks[0]:self.marks[1]]


def phase_ms(step: Dict) -> Dict[str, float]:
    """A step's time by phase: each of ``PHASES``, each launch, and the
    rest of the step as ``host``."""
    out: Dict[str, float] = {}
    for name, ms in step["spans"]:
        if name in PHASES or name.startswith("dispatch."):
            out[name] = out.get(name, 0.0) + ms
    out["host"] = step["ms"] - sum(out.values())
    return out


def _median(xs: List[float]) -> Optional[float]:
    return float(np.median(xs)) if xs else None


def median_step_ms(steps: List[Dict]) -> Optional[float]:
    return _median([s["ms"] for s in steps if s["decoded"]])


def host_ms_per_step(steps: List[Dict]) -> Optional[float]:
    return _median([s["ms"] - sum(ms for n, ms in s["spans"]
                                  if n in READBACKS)
                    for s in steps if s["decoded"]])


def stall_ms(steps: List[Dict]) -> Optional[float]:
    med = median_step_ms(steps)
    if med is None:
        return None
    return sum(max(0.0, s["ms"] - 2 * med) for s in steps)


def stalls(steps: List[Dict]) -> List[Dict]:
    """Each step over twice the median decode step, with each phase's time
    and excess over that phase's median in a decode step."""
    med = median_step_ms(steps)
    if med is None:
        return []
    decode = [phase_ms(s) for s in steps if s["decoded"]]
    names = sorted({n for p in decode for n in p})
    typical = {n: float(np.median([p.get(n, 0.0) for p in decode]))
               for n in names}
    t0 = steps[0]["t0"]
    out = []
    for s in steps:
        if s["ms"] <= 2 * med:
            continue
        phases = phase_ms(s)
        excess = {n: ms - typical.get(n, 0.0) for n, ms in phases.items()}
        phase = max(excess, key=excess.get)
        out.append({"at_s": s["t0"] - t0, "ms": s["ms"],
                    "excess_ms": s["ms"] - med, "phases_ms": phases,
                    "phase_excess_ms": excess, "phase": phase,
                    "phase_share": excess[phase] / (s["ms"] - med)})
    return out


def summarize(steps: List[Dict]) -> Dict:
    return {"steps": len(steps),
            "decode_steps": sum(1 for s in steps if s["decoded"]),
            "median_step_ms": median_step_ms(steps),
            "host_ms_per_step": host_ms_per_step(steps),
            "stall_ms": stall_ms(steps), "stalls": stalls(steps)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    xtrace.HOST_PREFIXES = xtrace.HOST_PREFIXES + ("serve.", "dispatch.")
    cell = run.lookup(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        log = StepSpans()
        t = time.perf_counter()
        # execute's hook on the built engine (the one faults.py plants by)
        res = run.execute(cell, seed, args.seconds, bool(args.trace),
                          fault=log.attach)
        print(json.dumps({
            "seed": seed, "trace": args.trace, "correct": res["correct"],
            "run_s": time.perf_counter() - t,
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "phases": summarize(log.window()), "device": res["device"],
            "breakdown": res.get("breakdown"), "info": res["info"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
