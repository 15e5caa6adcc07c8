"""Doorbell (dispatch) tracking.

In the paper, the doorbell write is the driver's final commit point for a
submission cycle; counting doorbell writes counts submission cycles, and the
watchpoint guarantees every one is observed.  On the JAX/PJRT stack the commit
point of a submission is the dispatch of a compiled executable.

:class:`DoorbellTracker` owns that dispatch boundary: callables wrapped by a
tracker ring its "doorbell" on every call, recording the submission timestamp,
the wall time to enqueue (dispatch, async) and optionally to complete, and the
argument payload bytes.  This is the measurement substrate for the CUDA-Graph
case study (dispatch counts ≙ doorbell writes) and for the Trainer's
submission accounting.

Every recorded cycle is also published as a ``dispatch`` event on the bound
or ambient :class:`~repro.core.session.TraceSession` (see that module);
standalone use without a session is unchanged.  A wrapped call also runs
inside a ``jax.profiler.TraceAnnotation`` named ``dispatch.<name>``, so a
profiler trace shows each launch's host side beside the device's programs.

A tracker may live as long as a long-running server, so it keeps only the
newest :data:`MAX_RECORDS` records; its counts and totals are running sums
over every cycle.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any, Callable, Deque, Dict, Optional

import jax

from .session import TraceSession, resolve_session

__all__ = ["DoorbellRecord", "DoorbellTracker", "payload_bytes",
           "MAX_RECORDS"]

#: How many of the newest records a tracker keeps.
MAX_RECORDS = 4096


def payload_bytes(tree: Any) -> int:
    """Bytes of array arguments in a pytree (the 'submission payload')."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nb = getattr(leaf, "nbytes", None)
        if nb is None:
            size = getattr(leaf, "size", 1)
            itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", 4)
            nb = size * itemsize
        total += int(nb)
    return total


@dataclasses.dataclass
class DoorbellRecord:
    """One submission cycle."""

    seq: int
    name: str
    t_submit: float            # perf_counter at dispatch
    dispatch_s: float          # time to enqueue (returns before completion)
    complete_s: float          # time to completion (if blocked)
    payload_bytes: int


class DoorbellTracker:
    """Counts and times submission cycles ("doorbell writes")."""

    def __init__(self, session: Optional[TraceSession] = None) -> None:
        self.records: Deque[DoorbellRecord] = collections.deque(
            maxlen=MAX_RECORDS)
        self._seq = 0
        self._session = session
        # per name, over every cycle: doorbells, dispatch_s, payload_bytes
        self._by_name: Dict[str, Dict[str, float]] = {}

    # -- wrapping ----------------------------------------------------------
    def wrap(self, fn: Callable, name: str = "dispatch",
             block: bool = False) -> Callable:
        """Wrap a (compiled/jitted) callable so each call rings the doorbell.

        With ``block=True`` the wrapper waits for completion and records the
        full duration; otherwise only the (async) dispatch time is recorded —
        the analogue of the doorbell write returning immediately while the
        GPU consumes the GPFIFO.
        """
        label = f"dispatch.{name}"

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with jax.profiler.TraceAnnotation(label):
                t0 = time.perf_counter()
                out = fn(*args, **kwargs)
                t1 = time.perf_counter()
                complete = 0.0
                if block:
                    jax.block_until_ready(out)
                    complete = time.perf_counter() - t0
            self._record(name, t0, t1 - t0, complete,
                         payload_bytes((args, kwargs)))
            return out
        return wrapped

    def ring(self, name: str = "manual", payload: int = 0) -> None:
        """Explicitly record a submission cycle."""
        t = time.perf_counter()
        self._record(name, t, 0.0, 0.0, payload)

    def _record(self, name: str, t0: float, disp: float, comp: float,
                payload: int) -> None:
        self.records.append(DoorbellRecord(
            seq=self._seq, name=name, t_submit=t0, dispatch_s=disp,
            complete_s=comp, payload_bytes=payload))
        self._seq += 1
        d = self._by_name.setdefault(name, {"doorbells": 0, "dispatch_s": 0.0,
                                            "payload_bytes": 0})
        d["doorbells"] += 1
        d["dispatch_s"] += disp
        d["payload_bytes"] += payload
        sess = resolve_session(self._session)
        if sess is not None:
            sess.emit("dispatch", name, dur_s=disp, complete_s=comp,
                      payload_bytes=payload, t=t0)

    # -- accounting (over every cycle, not only the records kept) ----------
    @property
    def count(self) -> int:
        return self._seq

    def count_for(self, name: str) -> int:
        return self._total("doorbells", name)

    def total_dispatch_s(self, name: Optional[str] = None) -> float:
        return self._total("dispatch_s", name)

    def total_payload(self, name: Optional[str] = None) -> int:
        return self._total("payload_bytes", name)

    def _total(self, key: str, name: Optional[str]):
        return sum(d[key] for n, d in self._by_name.items()
                   if name is None or n == name)

    def reset(self) -> None:
        self.records.clear()
        self._seq = 0
        self._by_name.clear()

    def summary(self) -> Dict[str, Any]:
        return {"total_doorbells": self.count,
                "by_name": {n: dict(d) for n, d in self._by_name.items()}}
