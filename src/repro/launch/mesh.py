"""Production meshes + the fleet-identity session helper.

Meshes are defined as FUNCTIONS so importing this module never touches jax
device state; the dry-run sets ``xla_force_host_platform_device_count``
before calling.  Axes:

  (data=16, model=16)            — one v5e pod slice, 256 chips
  (pod=2, data=16, model=16)     — two pods, 512 chips

:func:`fleet_session` is the one place launchers build their
:class:`~repro.core.session.TraceSession`: it stamps the session with
:func:`~repro.distributed.context.process_tags` (so every event carries
``host``/``process`` — the shard identity :mod:`repro.obs.aggregate`
merges by) and, when a trace path is given, attaches a
:class:`~repro.core.session.JsonlSink` at the per-process
:func:`~repro.distributed.context.shard_path`.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional, Tuple

import jax

__all__ = ["make_production_mesh", "make_mesh", "fleet_session",
           "enable_compile_cache", "SINGLE_POD", "MULTI_POD"]

SINGLE_POD = (16, 16)
MULTI_POD = (2, 16, 16)


def _make(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(data: int, model: int, pod: int = 1):
    """Arbitrary (pod ×) data × model mesh for tests/examples."""
    if pod > 1:
        return _make((pod, data, model), ("pod", "data", "model"))
    return _make((data, model), ("data", "model"))


def fleet_session(name: str, trace_path: Optional[str] = None
                  ) -> Tuple["object", Optional[str]]:
    """Build this process's fleet-identified :class:`TraceSession`.

    Returns ``(session, shard_jsonl_path)`` — the path is None without
    ``trace_path``, else the :func:`shard_path`-mangled per-process file
    (``trace.jsonl`` -> ``trace.p3.jsonl`` in a 4-process fleet) ready for
    ``python -m repro.obs.aggregate`` / ``python -m repro.obs.export``.
    """
    from ..core.session import TraceSession
    from ..distributed.context import process_tags, shard_path
    path = shard_path(trace_path) if trace_path else None
    return TraceSession(name=name, jsonl_path=path,
                        tags=process_tags()), path


#: Fixed persistent-cache location: a directory named from a temporary path,
#: a process id or the time would start every run with an empty cache.
REPO_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, already configures the cache
    and is left alone.  Otherwise the cache lives in ``<repo>/.jax_cache``.
    Call before the first compile: JAX fixes the cache on first use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_COMPILE_CACHE))
    return str(REPO_COMPILE_CACHE)
