"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --steps 50 \
        [--smoke] [--steps-per-launch 4] [--ckpt-dir /tmp/ckpt] \
        [--grad-compression int8] [--seq 256 --batch 8] \
        [--trace trace.jsonl] [--profile]

On this CPU container use ``--smoke`` (reduced config); on a real slice the
full config + production mesh apply (see launch/dryrun.py for the sharding).

``--trace PATH`` writes this process's fleet-identified JSONL shard (tagged
``host``/``process``, per-process filename) for ``repro.obs.aggregate`` /
``repro.obs.export``; ``--profile`` prints per-``train.step`` span
attribution (doorbells, payload, wall p50/p90/p99).
"""
from __future__ import annotations

import argparse

from ..configs import ARCHS, SMOKE_ARCHS
from ..configs.shapes import ShapeConfig
from ..runtime.trainer import Trainer
from ..tune.policy import load_policy_for
from .mesh import enable_compile_cache, fleet_session


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b", choices=list(ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps-per-launch", type=int, default=None,
                    help="unset -> auto-apply the tuned policy "
                         "(python -m repro.tune), else 4")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--grad-compression", default=None,
                    choices=[None, "int8"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write this process's JSONL trace shard "
                         "(fleet-tagged, per-process filename)")
    ap.add_argument("--profile", action="store_true",
                    help="print per-span command attribution after the run")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (SMOKE_ARCHS if args.smoke else ARCHS)[args.arch]
    shape = ShapeConfig("train", args.seq, args.batch, "train")
    spl = args.steps_per_launch
    if spl is None and load_policy_for(cfg, activate=False) is None:
        spl = 4                      # legacy CLI default when untuned
    session, shard = fleet_session("train", trace_path=args.trace)
    prof = None
    if args.profile:
        from ..obs.profile import SpanProfile
        prof = SpanProfile(name="train")
        session.add_sink(prof)
    tr = Trainer(cfg, shape, steps_per_launch=spl,
                 ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                 grad_compression=args.grad_compression,
                 peak_lr=args.lr, seed=args.seed, session=session)
    if tr.policy is not None:
        print(f"policy: {tr.policy.arch} knobs={tr.policy.knobs} "
              f"objective={tr.policy.objective.get('after')}")
    if args.ckpt_dir and tr.maybe_restore():
        print(f"restored at step {tr.step}")
    out = tr.train(args.steps)
    print(out)
    print(tr.submission_report())
    print(tr.trace_report(max_events=30))
    if prof is not None:
        print(prof.report())
    session.close()
    if shard:
        print(f"trace shard: {shard}")


if __name__ == "__main__":
    main()
