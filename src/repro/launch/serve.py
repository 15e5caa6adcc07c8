"""Serving launcher: batched greedy decode with multi-token launches.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
        [--tokens-per-launch 4] [--batch 4] [--new-tokens 16] [--continuous]

``--continuous`` serves the same requests through the continuous-batching
engine (queued admission, per-request KV slots) instead of one static
batch; ``python -m repro.launch.loadtest`` is the full traffic harness.
``--live [PORT]`` (with ``--continuous``) exposes the engine's live
session summary over HTTP while it runs (``GET /summary``,
``GET /stream`` — see :mod:`repro.obs.live`).

``--trace PATH`` writes a fleet-identified JSONL shard of the run
(``host``/``process`` tags, per-process filename) for
``repro.obs.aggregate`` / ``repro.obs.export``; ``--profile`` prints
per-span command attribution (``serve.request``, ``serve.decode_iter``,
``serve.prefill``) after the run.
"""
from __future__ import annotations

import argparse

import numpy as np

from ..configs import ARCHS, SMOKE_ARCHS
from ..runtime.server import ContinuousBatchingServer, Request, Server
from ..tune.policy import load_policy_for
from .mesh import enable_compile_cache, fleet_session


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b", choices=list(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--tokens-per-launch", type=int, default=None,
                    help="unset -> auto-apply the tuned policy "
                         "(python -m repro.tune), else 4")
    ap.add_argument("--continuous", action="store_true",
                    help="serve through the continuous-batching engine")
    ap.add_argument("--kv", default="dense", choices=("dense", "paged"),
                    help="with --continuous: KV-cache backend")
    ap.add_argument("--kv-page-tokens", type=int, default=None,
                    help="paged page size in tokens (unset -> tuned/16)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="max tokens per prefill launch (unset -> "
                         "tuned/off)")
    ap.add_argument("--sched", default="fifo",
                    choices=("fifo", "priority", "fair"),
                    help="with --continuous: admission scheduling policy")
    ap.add_argument("--requests", type=int, default=None,
                    help="request count for --continuous (default: batch)")
    ap.add_argument("--live", type=int, default=None, nargs="?", const=0,
                    metavar="PORT",
                    help="with --continuous: serve the live summary over "
                         "HTTP while the engine runs")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write this process's JSONL trace shard "
                         "(fleet-tagged, per-process filename)")
    ap.add_argument("--profile", action="store_true",
                    help="print per-span command attribution after the run")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (SMOKE_ARCHS if args.smoke else ARCHS)[args.arch]
    tpl = args.tokens_per_launch
    if tpl is None and load_policy_for(cfg, activate=False) is None:
        tpl = 4                      # legacy CLI default when untuned
    session, shard = fleet_session("serve", trace_path=args.trace)
    prof = None
    if args.profile:
        from ..obs.profile import SpanProfile
        prof = SpanProfile(name="serve")
        session.add_sink(prof)
    if args.continuous:
        srv = ContinuousBatchingServer(
            cfg, batch_size=args.batch, max_seq=args.max_seq,
            tokens_per_launch=tpl, seed=args.seed, session=session,
            kv=args.kv, kv_page_tokens=args.kv_page_tokens,
            prefill_chunk=args.prefill_chunk, sched=args.sched)
    else:
        srv = Server(cfg, batch_size=args.batch, max_seq=args.max_seq,
                     tokens_per_launch=tpl, seed=args.seed, session=session)
    if srv.policy is not None:
        print(f"policy: {srv.policy.arch} knobs={srv.policy.knobs} "
              f"objective={srv.policy.objective.get('after')}")
    rng = np.random.default_rng(args.seed)
    n = (args.requests or args.batch) if args.continuous else args.batch
    reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                    size=args.prompt_len).astype(np.int32),
                    max_new_tokens=args.new_tokens)
            for i in range(n)]
    if args.continuous:
        live_srv = None
        if args.live is not None:
            live_srv = srv.start_live_endpoint(port=args.live)
            print(f"live summary endpoint: {live_srv.url}/summary")
        for r in reqs:
            srv.submit(r)
        try:
            out = srv.run()
        finally:
            if live_srv is not None:
                srv.stop_live_endpoint()
    else:
        out = srv.serve(reqs)
    print(out)
    for r in reqs[:2]:
        print(f"req {r.uid}: {r.tokens}")
    print(srv.session.report(max_events=30))
    if prof is not None:
        print(prof.report())
    session.close()
    if shard:
        print(f"trace shard: {shard}")


if __name__ == "__main__":
    main()
