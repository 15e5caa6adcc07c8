#!/usr/bin/env python3
"""On-chip smoke test: serve full-width Gemma-2B on one TPU and check it.

    python3 chip_smoke.py

Run from the root of a checkout, on a host whose first JAX device is a TPU.
It refuses any other platform: nothing here falls back to the CPU.  One
process, three phases; any failure raises and exits nonzero:

1. serve   -- ``repro.launch.loadtest`` drives the continuous-batching
   engine with the published ``gemma-2b`` config (random weights from a
   seed), once with the dense KV backend and once paged with chunked
   prefill and a shared prompt prefix.  Every engine knob is passed on the
   command line, so no tuned policy file is read.  ``--verify`` replays
   requests through one-shot ``Server.serve`` and reports whether the
   tokens are bit-identical.
2. logits  -- in float32 at the highest matmul precision, the logits of
   prefill-then-decode through the KV cache (and of chunked prefill) are
   compared with the model's full forward pass over the same tokens.
3. kernels -- every Pallas kernel runs compiled (not interpreted) at real
   widths and is compared with its ``ref.py`` oracle.

Timings printed here are smoke readings of one cold run, not benchmark
numbers.  The last line of standard output is the JSON result.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time
from typing import Dict, List

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
SEED = 0

# one chip's serving shape for the smoke run
SLOTS, MAX_SEQ, T = 8, 2048, 4
REQUESTS, PROMPT_LENS, NEW_TOKENS = 16, "64,256,512", 32
VERIFY = 4
PAGE_TOKENS, PREFILL_CHUNK, PREFIX_LEN = 64, 256, 64

# Phase 2: both sides run the same float32 weights at HIGHEST precision, so
# they differ only in summation order (masked max_seq cache vs causal
# attention over the prompt; chunked vs whole prefill).  That moves logits
# by about 1e-5.  2e-3 is far below bf16's relative step (7.8e-3), so a
# misindexed cache row or a bf16 leak (errors of 1e-2 and up) still fails.
LOGIT_TOL = 2e-3
# Phase 3: the per-dtype tolerances of tests/test_kernels.py.
KERNEL_TOL = {"flash_attention": 3e-2, "ssd_scan": 6e-2, "rms_norm": 2e-2}


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileLog:
    """Backend compile seconds per jitted function, from JAX's monitoring."""

    def __init__(self) -> None:
        self.by_fn: Dict[str, List[float]] = {}
        self.cache_hits = 0

    def on_duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.by_fn.setdefault(kw.get("fun_name", "?"), []).append(secs)

    def on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> Dict[str, object]:
        """Summary since the last call, then reset."""
        out = {"programs": sum(len(v) for v in self.by_fn.values()),
               "seconds": sum(sum(v) for v in self.by_fn.values()),
               "cache_hits": self.cache_hits,
               "slowest": sorted(((max(v), k) for k, v in self.by_fn.items()),
                                 reverse=True)[:5]}
        self.by_fn, self.cache_hits = {}, 0
        return out


def report_memory(dev, label: str) -> None:
    stats = dev.memory_stats() or {}
    gib = 1 << 30
    print(f"[{label}] memory: peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use')} "
          f"({stats.get('peak_bytes_in_use', 0) / gib:.2f} GiB), "
          f"bytes_in_use={stats.get('bytes_in_use')}, "
          f"bytes_limit={stats.get('bytes_limit')}")


def report_compiles(log: CompileLog, label: str) -> None:
    c = log.take()
    print(f"[{label}] compile (smoke reading, cold unless cache hits): "
          f"{c['programs']} programs, {c['seconds']:.1f} s backend compile, "
          f"{c['cache_hits']} persistent-cache hits")
    for secs, fn in c["slowest"]:
        print(f"[{label}]   {fn}: {secs:.1f} s")


# ----------------------------------------------------------------- phase 1
def loadtest_argv(kv: str, json_path: str) -> List[str]:
    argv = ["--arch", "gemma-2b", "--full", "--batch", str(SLOTS),
            "--max-seq", str(MAX_SEQ), "--tokens-per-launch", str(T),
            "--max-pending", "256", "--admission", "reject",
            "--sched", "fifo", "--requests", str(REQUESTS),
            "--rate", "100", "--prompt-lens", PROMPT_LENS,
            "--new-tokens", str(NEW_TOKENS), "--seed", str(SEED),
            "--verify", str(VERIFY), "--json", json_path, "--kv", kv]
    if kv == "paged":
        argv += ["--kv-page-tokens", str(PAGE_TOKENS),
                 "--prefill-chunk", str(PREFILL_CHUNK),
                 "--prefix-len", str(PREFIX_LEN)]
    else:
        argv += ["--prefill-chunk", "0", "--prefix-len", "0"]
    return argv


def phase_serve(kv: str) -> bool:
    """One loadtest run; returns whether its tokens matched one-shot serve."""
    from repro.launch import loadtest

    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"loadtest_{kv}.json")
    argv = loadtest_argv(kv, path)
    print(f"[serve/{kv}] python -m repro.launch.loadtest {' '.join(argv)}")
    t0 = time.perf_counter()
    rc = loadtest.main(argv)
    wall = time.perf_counter() - t0
    with open(path) as f:
        rec = json.load(f)
    m, verified = rec["metrics"], rec["verified"]
    # loadtest exits 1 only when --verify finds a token mismatch; that is a
    # reported finding here, anything else is a failure
    check(rc == 0 or (rc == 1 and not verified["ok"]),
          f"loadtest --kv {kv} returned {rc}")
    check(m["requests"] == REQUESTS and m["completed"] == REQUESTS,
          f"{kv}: {m['completed']}/{REQUESTS} requests completed "
          f"(evicted={m['evicted']} rejected={m['rejected']})")
    check(m["new_tokens"] == REQUESTS * NEW_TOKENS,
          f"{kv}: {m['new_tokens']} tokens, expected "
          f"{REQUESTS * NEW_TOKENS}")
    check(all(t["status"] == "done" and t["n_tokens"] == NEW_TOKENS
              for t in rec["tickets"]), f"{kv}: a ticket did not finish")
    kvs = rec["kv"]
    if kv == "paged":
        check(kvs["prefix_hits"] > 0, "paged: no shared-prefix page reuse")
        check(kvs["chunked_prompts"] > 0, "paged: no prompt was chunked")
    print(f"[serve/{kv}] smoke reading, not a benchmark: wall {wall:.1f} s "
          f"including compiles; {m['new_tokens']} tokens in "
          f"{m['doorbells']} doorbells; prefill launches "
          f"{kvs['prefill_launches']}")
    print(f"[serve/{kv}] bit-identical to one-shot Server.serve: "
          f"{verified['ok']} ({VERIFY} requests)")
    return verified["ok"]


# ----------------------------------------------------------------- phase 2
def phase_logits(cfg, prompt_len: int = 64, n_decode: int = 8) -> float:
    """Max |logit error| of the cached paths against the full forward."""
    import jax
    import numpy as np

    from repro.models import get_model
    from repro.models.layers import unembed

    model = get_model(dataclasses.replace(cfg, param_dtype="float32"))
    rng = np.random.default_rng(SEED)
    toks = jax.numpy.asarray(rng.integers(
        0, cfg.vocab_size, size=(1, prompt_len + n_decode)), np.int32)
    half = prompt_len // 2
    errs = {}
    with jax.default_matmul_precision("highest"):
        params = jax.jit(model.init_params)(jax.random.PRNGKey(SEED))

        @jax.jit
        def forward(p, t):
            x, _ = model.hidden_states(p, t, mode="eval")
            return unembed(p["emb"], x)

        full = np.asarray(forward(params, toks))[0]         # [S, V]
        check(full.shape == (prompt_len + n_decode, cfg.vocab_size)
              and np.isfinite(full).all(), "full forward: bad logits")

        prefill = jax.jit(model.prefill, static_argnums=2)
        decode = jax.jit(model.decode_step)
        extend = jax.jit(model.prefill_extend)
        state, lg = prefill(params, toks[:, :prompt_len], MAX_SEQ)
        cached = [np.asarray(lg)[0, -1]]
        for i in range(n_decode - 1):
            pos = prompt_len + i
            state, lg = decode(params, state, toks[:, pos:pos + 1])
            cached.append(np.asarray(lg)[0, -1])
        cached = np.stack(cached)
        ref = full[prompt_len - 1:prompt_len - 1 + n_decode]
        errs["prefill+decode"] = float(np.max(np.abs(cached - ref)))

        st = model.init_decode_state(1, MAX_SEQ)
        st, _ = extend(params, st, toks[:, :half])
        st, lg = extend(params, st, toks[:, half:prompt_len])
        errs["chunked prefill"] = float(np.max(np.abs(
            np.asarray(lg)[0, -1] - full[prompt_len - 1])))
        scale = float(np.max(np.abs(full)))
    del params, state, st
    for name, err in errs.items():
        print(f"[logits] {name}: max |err| {err:.3e} over logits up to "
              f"{scale:.2f} (tolerance {LOGIT_TOL})")
        check(np.isfinite(err) and err <= LOGIT_TOL,
              f"{name} logits differ from the full forward by {err:.3e}")
    return max(errs.values())


# ----------------------------------------------------------------- phase 3
def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import interpret_mode
    from repro.kernels.dma_copy.ops import dma_copy
    from repro.kernels.dma_copy.ref import dma_copy_ref
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    from repro.kernels.rms_norm.ops import rms_norm_fused
    from repro.kernels.rms_norm.ref import rms_norm_ref
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_scan_ref

    check(not interpret_mode(), "Pallas kernels would run interpreted")
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))

    def normal(shape, dtype, scale=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def compare(name, out, ref):
        out = np.asarray(out, np.float32)
        ref = np.asarray(ref, np.float32)
        check(out.shape == ref.shape and np.isfinite(out).all(),
              f"{name}: bad output {out.shape}")
        tol = KERNEL_TOL[name]
        err = np.abs(out - ref)
        # the assert_allclose criterion, |err| <= tol + tol*|ref|, as a ratio
        worst = float(np.max(err / (tol + tol * np.abs(ref))))
        print(f"[kernels] {name}: compiled, max |err| {np.max(err):.3e} over "
              f"outputs up to {np.max(np.abs(ref)):.2f}; worst element at "
              f"{worst:.3f} of its tolerance (atol = rtol = {tol})")
        check(worst <= 1.0, f"{name}: differs from its ref.py oracle")

    with jax.default_matmul_precision("highest"):      # exact oracles
        # Gemma-2B attention widths at S=4096
        q, k, v = (normal((1, 4096, 8, 256), jnp.bfloat16) for _ in range(3))
        compare("flash_attention", flash_attention(q, k, v, causal=True),
                flash_attention_ref(q, k, v, causal=True))
        # mamba2-780m SSD widths: H=48, P=64, N=128, chunk 256
        B, S, H, P, N = 1, 4096, 48, 64, 128
        xh = normal((B, S, H, P), jnp.bfloat16)
        dt = jnp.abs(normal((B, S, H), jnp.float32))
        A = -jnp.abs(normal((H,), jnp.float32))
        Bc, Cc = normal((B, S, N), jnp.bfloat16), normal((B, S, N),
                                                         jnp.bfloat16)
        compare("ssd_scan", ssd_scan(xh, dt, A, Bc, Cc, chunk=256)[0],
                ssd_scan_ref(xh, dt, A, Bc, Cc, chunk=256)[0])
        x = normal((4096, 2048), jnp.bfloat16)
        s = normal((2048,), jnp.bfloat16, 0.1)
        compare("rms_norm", rms_norm_fused(x, s), rms_norm_ref(x, s))
        x = normal((8192, 2048), jnp.bfloat16)
        for mode in ("pipelined", "explicit"):
            y = dma_copy(x, mode=mode)
            check(bool(jnp.array_equal(y, dma_copy_ref(x))),
                  f"dma_copy[{mode}]: copy differs from its input")
            print(f"[kernels] dma_copy[{mode}]: compiled, bit-exact copy")


def main() -> int:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.configs import ARCHS
        from repro.launch.mesh import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: run from a checkout of the repo ({e})",
              file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r} "
              f"({dev.device_kind}); refusing to run", file=sys.stderr)
        return 1
    print(f"device: platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__}")
    print(f"compile cache: {enable_compile_cache()}")
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log.on_duration)
    jax.monitoring.register_event_listener(log.on_event)

    identical = {}
    for kv in ("dense", "paged"):
        identical[kv] = phase_serve(kv)
        report_compiles(log, f"serve/{kv}")
        gc.collect()                    # engines and weights die with loadtest
        report_memory(dev, f"serve/{kv}")

    err = phase_logits(ARCHS["gemma-2b"])
    report_compiles(log, "logits")
    gc.collect()
    report_memory(dev, "logits")

    phase_kernels()
    report_compiles(log, "kernels")
    report_memory(dev, "kernels")

    print(f"phases passed: serve/dense serve/paged logits "
          f"(max err {err:.3e}) kernels; bit-identical to one-shot serve: "
          + ", ".join(f"{kv}={ok}" for kv, ok in identical.items()))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
