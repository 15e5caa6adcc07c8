"""Benchmark harness: one section per paper table/figure, JSON artifact out.

  bench_dma        — Fig. 6 + Table 2 (inline vs direct DMA protocols)
  bench_graphs     — Fig. 7/9/10 (graph launch scaling, footprint law)
  bench_submission — §6.2/§7 (stage decomposition, multi-step economy)
  bench_policy     — tuned-policy before/after (python -m repro.tune)
  bench_loadtest   — continuous-batching serve under Poisson traffic
  bench_kv         — dense vs paged KV backends on shared-prefix traffic
  bench_kernels    — per-kernel interpret-mode sanity timings

Prints ``name,value...`` CSV blocks (unchanged), and additionally writes a
machine-readable artifact (``--out``, default ``BENCH_10.json``) recording
section -> rows (typed by the section header), the unified TraceSession
summary, and the active tuned policy with its before/after objective — one
point of the ROADMAP's perf trajectory, regenerated per PR and gated in CI
by ``python -m repro.obs.trajectory`` against the newest committed
``BENCH_*.json`` (deterministic count metrics gate hard via
``--gate-counts``; timings stay warn-only on shared runners).  The scored
metrics are also appended to the persistent store
(``results/metrics/bench.jsonl``; disable with ``--no-store``) so
``python -m repro.obs.store trend --kind bench`` answers across runs.
``--quick`` shrinks every sweep to CI scale.

ONE :class:`repro.core.TraceSession` spans every section — installed as the
ambient session and passed explicitly where a section builds its own objects
— so the final block is the unified, submission-ordered event summary across
DMA, graph-launch, trainer, and policy benchmarks.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--out BENCH_10.json]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Any, Dict, List

PR_NUMBER = 10


def _parse_cell(v: str) -> Any:
    if v == "":
        return None
    try:
        return int(v)
    except ValueError:
        pass
    try:
        return float(v)
    except ValueError:
        return v


def _rows_to_json(header: str, rows: List[str]) -> List[Dict[str, Any]]:
    """CSV rows -> list of {column: typed value} dicts, keyed by header."""
    cols = header.split(",")
    out = []
    for r in rows:
        cells = r.split(",")
        cells += [""] * (len(cols) - len(cells))
        out.append({c: _parse_cell(v) for c, v in zip(cols, cells)})
    return out


def bench_kernels_rows():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.ssd_scan.ops import ssd_scan
    rows = []
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 256, 4, 64)), jnp.float32)
    t0 = time.perf_counter()
    jax.block_until_ready(flash_attention(q, q, q))
    rows.append(f"flash_attention_interp_256,{(time.perf_counter()-t0)*1e3:.1f}")
    xh = jnp.asarray(rng.normal(size=(1, 256, 4, 32)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(1, 256, 4))), jnp.float32)
    A = jnp.asarray(-np.ones(4), jnp.float32)
    Bc = jnp.asarray(rng.normal(size=(1, 256, 16)), jnp.float32)
    t0 = time.perf_counter()
    y, _ = ssd_scan(xh, dt, A, Bc, Bc, chunk=64)
    jax.block_until_ready(y)
    rows.append(f"ssd_scan_interp_256,{(time.perf_counter()-t0)*1e3:.1f}")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=f"BENCH_{PR_NUMBER}.json",
                    help="JSON artifact path ('' to skip writing)")
    ap.add_argument("--quick", action="store_true",
                    help="CI-scale sweeps (fewer sizes/chains/steps)")
    ap.add_argument("--arch", default="gemma-2b",
                    help="arch whose tuned policy the policy section benches")
    ap.add_argument("--no-store", action="store_true",
                    help="skip appending scored metrics to the persistent "
                         "metrics store (results/metrics/bench.jsonl)")
    args = ap.parse_args()

    from repro.core import TraceSession
    from repro.launch.mesh import enable_compile_cache
    from repro.tune.policy import load_policy

    from . import (bench_dma, bench_graphs, bench_kv, bench_loadtest,
                   bench_policy, bench_submission)

    enable_compile_cache()

    sections: Dict[str, Dict[str, Any]] = {}

    def _section(key: str, title: str, header: str, rows: List[str]) -> None:
        print(f"# === {title} ===")
        print(header)
        for r in rows:
            print(r)
        sys.stdout.flush()
        sections[key] = {"title": title, "header": header.split(","),
                         "rows": _rows_to_json(header, rows)}

    with TraceSession(name="benchmarks") as sess:
        _section("dma", "DMA protocols (Fig.6 / Table 2)", bench_dma.HEADER,
                 bench_dma.run(quick=args.quick))
        _section("graphs", "Graph launch scaling (Fig.7/9/10)",
                 bench_graphs.HEADER,
                 bench_graphs.run(quick=args.quick, session=sess))
        _section("submission", "Submission stage split (§6.2/§7)",
                 bench_submission.HEADER,
                 bench_submission.run(quick=args.quick, session=sess))
        _section("policy", "Tuned submission policy (repro.tune)",
                 bench_policy.HEADER,
                 bench_policy.run(arch=args.arch, quick=args.quick,
                                  session=sess))
        _section("loadtest", "Continuous-batching serve (Poisson replay)",
                 bench_loadtest.HEADER,
                 bench_loadtest.run(arch=args.arch, quick=args.quick,
                                    session=sess))
        _section("kv", "KV backends: dense vs paged (shared-prefix)",
                 bench_kv.HEADER,
                 bench_kv.run(arch=args.arch, quick=args.quick,
                              session=sess))
        _section("kernels", "Kernel interpret-mode timings", "name,ms",
                 bench_kernels_rows())
    summary = sess.summary()
    sink_stats = sess.sink_stats()
    print("# === Unified trace session ===")
    print(json.dumps(summary, indent=2, sort_keys=True))

    if args.out:
        from repro.configs import SMOKE_ARCHS
        cfg = SMOKE_ARCHS.get(args.arch)
        pol = load_policy(getattr(cfg, "name", None) or args.arch)
        artifact = {
            "pr": PR_NUMBER,
            "created_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "quick": bool(args.quick),
            "arch": args.arch,
            "sections": sections,
            "session_summary": summary,
            "sink_stats": sink_stats,
            "policy": pol.to_dict() if pol is not None else None,
            "tuning": ({"before": pol.objective.get("before"),
                        "after": pol.objective.get("after"),
                        "improvement": pol.objective.get("improvement"),
                        "knobs": pol.knobs}
                       if pol is not None else None),
        }
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.out}")

        if not args.no_store:
            # one trajectory point per run in the persistent store — the
            # same scored metrics the trajectory gate diffs, queryable
            # across runs with `python -m repro.obs.store trend --kind
            # bench` / `python -m repro.obs.trajectory --store bench`
            try:
                from repro.obs.store import MetricsStore
                from repro.obs.trajectory import extract_metrics
                scored = {k: v for k, (v, _d)
                          in extract_metrics(artifact).items()}
                rec = MetricsStore().append(
                    "bench", scored,
                    meta={"pr": PR_NUMBER, "quick": bool(args.quick),
                          "arch": args.arch, "out": args.out})
                print(f"# stored {len(scored)} metrics as run {rec.run_id}")
            except OSError as e:
                print(f"# metrics store unavailable ({e}); skipped")


if __name__ == "__main__":
    main()
