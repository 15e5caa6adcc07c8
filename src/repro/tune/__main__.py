"""CLI: search the exposed submission knobs and persist a policy.

    PYTHONPATH=src python -m repro.tune --arch gemma-2b \
        [--workloads dma,serve,train] [--rounds 2] [--full] \
        [--policy-dir results/policies] [--x64] [--host-devices N]

The environment preset (XLA flags, host device count, x64) is applied BEFORE
the first JAX initialization and recorded in the policy, Snippet-1 style.
After tuning, ``--verify`` (default) re-runs the serve workload with the
knobs left unset — exercising the auto-apply path Trainer/Server use — and
prints the TraceSession summary so the before/after objective is visible.
"""
from __future__ import annotations

import argparse
import json


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="python -m repro.tune")
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--full", action="store_true",
                    help="tune the full published config (default: smoke)")
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--workloads", default="dma,serve,train",
                    help="comma-separated subset of dma,serve,train,kv "
                         "(kv tunes the paged KV manager's kv_page_tokens + "
                         "prefill_chunk; opt-in)")
    ap.add_argument("--policy-dir", default=None)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--serve-mode", default="oneshot",
                    choices=("oneshot", "continuous"),
                    help="measure tokens_per_launch on a one-shot batch or "
                         "under the continuous-batching engine")
    ap.add_argument("--train-steps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    # environment preset (applied before first JAX init)
    ap.add_argument("--x64", action="store_true")
    ap.add_argument("--host-devices", type=int, default=None)
    ap.add_argument("--xla-flags", default="")
    ap.add_argument("--platform", default=None)
    ap.add_argument("--no-verify", dest="verify", action="store_false")
    args = ap.parse_args(argv)

    from .env import EnvPreset
    preset = EnvPreset(host_device_count=args.host_devices,
                       xla_flags=args.xla_flags,
                       x64=args.x64 or None, platform=args.platform)
    preset.apply()

    from .autotune import WorkloadSpec, tune
    spec = WorkloadSpec(batch=args.batch, new_tokens=args.new_tokens,
                        max_seq=args.max_seq, train_steps=args.train_steps,
                        serve_mode=args.serve_mode, seed=args.seed)
    workloads = tuple(w for w in args.workloads.split(",") if w)
    policy, result, path = tune(
        args.arch, smoke=not args.full, rounds=args.rounds,
        workloads=workloads, spec=spec, env_preset=preset,
        policy_dir=args.policy_dir)

    if args.verify and "serve" in workloads:
        _verify(args, policy)


def _verify(args, policy) -> None:
    """Auto-apply check: a fresh Server with the knob unset picks up the
    persisted policy; its steady-state summary shows the tuned objective."""
    import numpy as np

    from ..configs import ARCHS, SMOKE_ARCHS
    from ..core.session import TraceSession
    from .objective import Objective, metrics_from_summary
    from ..runtime.server import Request, Server

    cfg = (SMOKE_ARCHS if not args.full else ARCHS)[args.arch]
    rng = np.random.default_rng(args.seed)

    def requests():
        return [Request(i, rng.integers(0, cfg.vocab_size,
                                        size=4).astype(np.int32),
                        max_new_tokens=args.new_tokens)
                for i in range(args.batch)]

    with TraceSession(name="tune_verify") as sess:
        srv = Server(cfg, batch_size=args.batch, max_seq=args.max_seq,
                     seed=args.seed, session=sess)   # tokens_per_launch unset
        srv.serve(requests())                        # warm
        before = sess.summary()
        out = srv.serve(requests())
        summary = sess.summary()
    m = metrics_from_summary(summary, before, tokens=out["new_tokens"])
    print(f"verify: auto-applied tokens_per_launch={srv.T} "
          f"(policy says {policy.knob('tokens_per_launch')})")
    print(f"verify: objective={Objective().score(m):.3e} s/token  "
          f"doorbells/token={m.doorbells_per_token:.3f}  "
          f"dispatch={m.dispatch_s * 1e3:.2f}ms")
    print("verify: session summary:")
    print(json.dumps({k: summary[k] for k in
                      ("by_kind", "dur_s_by_kind", "total_dispatch_s")},
                     indent=2, sort_keys=True))


if __name__ == "__main__":
    main()
