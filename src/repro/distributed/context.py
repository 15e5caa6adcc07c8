"""Ambient mesh context for model-internal shard_map blocks.

Model code (e.g. the shard_map MoE) needs the active mesh + data-parallel
axis names; launchers set them here.  Kept explicit (not jax's global mesh)
so models stay traceable without a mesh for single-device tests.

Also hosts :func:`shard_map` (``jax.shard_map`` with keyword specs) and
the process-identity helpers (:func:`process_info`,
:func:`process_tags`) that fleet launchers use to tag their per-process
:class:`~repro.core.session.TraceSession` so JSONL shards identify
themselves to :mod:`repro.obs.aggregate`.
"""
from __future__ import annotations

import os
import socket
from typing import Any, Dict, Optional, Tuple

import jax

_MESH = None
_DP_AXES: Tuple[str, ...] = ()

__all__ = ["set_mesh", "get_mesh", "dp_axes_active", "shard_map",
           "process_info", "process_tags", "shard_path"]


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def set_mesh(mesh, dp_axes: Tuple[str, ...]) -> None:
    global _MESH, _DP_AXES
    _MESH = mesh
    _DP_AXES = tuple(dp_axes)


def get_mesh():
    return _MESH


def dp_axes_active() -> Tuple[str, ...]:
    return _DP_AXES


def process_info() -> Dict[str, Any]:
    """This process's place in the fleet (single-process -> 0 of 1).

    ``REPRO_PROCESS_ID`` / ``REPRO_PROCESS_COUNT`` override the jax runtime
    view — multi-process *simulations* (one host, N launched processes,
    e.g. the two-process aggregation example) identify themselves that way
    without initializing jax.distributed.
    """
    env_idx = os.environ.get("REPRO_PROCESS_ID")
    if env_idx is not None:
        idx = int(env_idx)
        count = int(os.environ.get("REPRO_PROCESS_COUNT", idx + 1))
    else:
        idx, count = jax.process_index(), jax.process_count()
    return {"host": socket.gethostname(), "process": int(idx),
            "process_count": int(count)}


def process_tags() -> Dict[str, Any]:
    """Session tags for this process: ``TraceSession(tags=process_tags())``.

    Every event the session emits then carries ``host``/``process`` in its
    ``meta`` — the shard identity :mod:`repro.obs.aggregate` merges by.
    """
    info = process_info()
    return {"host": info["host"], "process": info["process"]}


def shard_path(base: str) -> str:
    """Per-process JSONL shard path: ``trace.jsonl`` -> ``trace.p3.jsonl``.

    Identity function for a single-process fleet, so single-host CLIs can
    use it unconditionally.
    """
    info = process_info()
    if info["process_count"] <= 1:
        return base
    root, ext = os.path.splitext(base)
    return f"{root}.p{info['process']}{ext or '.jsonl'}"
