#!/usr/bin/env python3
"""On-chip serving benchmark: one cell, one seed, one process.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a host with a TPU.  The cell is looked up
by name in ``BENCHMARK.json``; its configuration, traffic mix, metric
readers and correctness limit are files under this directory, found by
name, and its architecture's plug-in is ``arch/<arch>.py`` (see
``arch/dense.py``).  The run:

1. refuses to run without a TPU (or with fewer chips than the cell asks);
2. builds the continuous-batching engine with every knob from the
   configuration file, and gives it weights made on the device from the seed;
3. warms every prefill, extend and decode shape the mix implies;
4. drives the engine from its own loop for ``--seconds``: an open loop
   submits each request when it is due, a closed batch queues them all at
   the start; every latency is timed on this loop's clock from the due time;
5. with ``--trace 1``, records a profiler trace of a few seconds inside the
   window and reduces it; the loop's clock stands still while the profiler
   starts and stops, so the requests it stalls are timed as if it had not;
6. frees the engine and compares a sample of the served tokens with the
   float32 reference;
7. prints one JSON line: ``correct``, ``attempted``, ``failed``,
   ``metrics``, ``device`` (and ``breakdown`` when traced), ending with the
   numbers compared and their limits under ``checks``.

``--rate`` overrides the mix's arrival rate (for a rate sweep) and
``--control`` puts the fp8 control's picks in place of the served tokens
(which must then fail the check); neither is part of any cell.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

import check  # noqa: E402
import readers  # noqa: E402
import schedule  # noqa: E402

ARCH_DIR = os.path.join(HERE, "arch")
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
DRAIN_S = 60.0          # how long past the window a due request may take
SAMPLE_TOKENS = 512     # served tokens the check compares, at least
SAMPLE_REQUESTS = 8     # requests the check compares, at most
# A program lowered: compiled, or loaded from the persistent cache (which a
# backend-compile event alone would miss once the cache is warm).
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_PATH = ("/jax/core/compile/", "/jax/compilation_cache/")


class NoChip(RuntimeError):
    pass


# -- the cell, by name -------------------------------------------------------
def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def lookup(name: str) -> Dict[str, Any]:
    """The cell ``name`` with its configuration, its architecture's plug-in,
    mix, limits and metrics."""
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def applies(m: Dict) -> bool:
        if "workloads" in m:
            return name in m["workloads"]
        if "moves" not in m:
            return True
        moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
        return applies(moved)

    cfg = _json(os.path.join(ROOT, conf["file"]))
    return {
        "name": name,
        "chips": int(cell["chips"]),
        "config": cfg,
        "arch": load_arch(cfg),
        "mix": _json(os.path.join(HERE, "traffic", cell["traffic"] + ".json")),
        "limits": _json(os.path.join(HERE, "limits", name + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str) -> Callable:
    return _module(os.path.join(HERE, "metrics", name + ".py"),
                   "metric_" + name.replace(".", "_")).read


def load_arch(cfg: Dict):
    """The plug-in ``arch/<arch>.py`` that the configuration names."""
    if "arch" not in cfg:
        raise SystemExit(f"configuration {cfg.get('name')!r} names no arch; "
                         f"its plug-in would be {ARCH_DIR}/<arch>.py")
    arch = str(cfg["arch"])
    path = os.path.join(ARCH_DIR, arch + ".py")
    if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_-]*", arch) or \
            not os.path.isfile(path):
        raise SystemExit(f"configuration {cfg.get('name')!r} names arch "
                         f"{arch!r}, but there is no plug-in {path}")
    return _module(path, "arch_" + arch)


# -- the device ---------------------------------------------------------------
def require_chips(chips: int) -> None:
    import jax
    devs = jax.devices()
    kind = getattr(devs[0], "device_kind", "?")
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s) of kind {kind!r}")


def enable_cache() -> None:
    """JAX's persistent compile cache, at a fixed path in the checkout."""
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_info() -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    stats = devs[0].memory_stats() or {}
    return {"platform": devs[0].platform,
            "kind": getattr(devs[0], "device_kind", "?"),
            "count": len(devs),
            "memory_peak_bytes": int(max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devs)) if stats else None}


def device_memory() -> Dict[str, int]:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats[k] for k in ("bytes_in_use", "largest_free_block_bytes",
                                  "num_allocs") if k in stats}


def peaks_for(kind: str, required: bool) -> Dict[str, float]:
    table = _json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        if required:
            raise NoChip(f"no peaks for device kind {kind!r} in peaks.json")
        return {"bf16_flops_per_s": float("nan"),
                "hbm_bytes_per_s": float("nan")}
    return table[kind]


# -- the engine ---------------------------------------------------------------
class SpanLog:
    """Session sink: keeps the engine's prefill and decode-iteration span
    closes, in order."""

    NAMES = ("serve.prefill", "serve.prefill_chunk", "serve.decode_iter")

    def __init__(self) -> None:
        self.events: List[Dict] = []

    def emit(self, ev) -> None:
        if ev.name == "obs.span" and ev.meta.get("span") in self.NAMES:
            self.events.append(ev.meta)


def build_engine(cfg: Dict, arch, seed: int, spans: SpanLog):
    """The engine with every knob from the configuration, holding weights
    that the plug-in ``arch`` makes from ``seed``; returns
    ``(engine, weights)``."""
    import jax
    from repro.core.session import TraceSession
    from repro.runtime.server import ContinuousBatchingServer
    e = cfg["engine"]
    session = TraceSession(name="bench")
    session.add_sink(spans)
    seed32 = int(np.random.SeedSequence(
        schedule.seed_words(seed)).generate_state(1)[0])
    eng = ContinuousBatchingServer(
        arch.model_config(cfg), batch_size=int(e["slots"]),
        max_seq=int(e["max_seq"]),
        tokens_per_launch=int(e["tokens_per_launch"]),
        seed=seed32 & 0x7FFFFFFF,
        session=session, max_pending=int(e["max_pending"]),
        admission=e["admission"], kv_page_tokens=int(e["page_tokens"]),
        kv_pages=e.get("pages"), prefill_chunk=int(e["prefill_chunk"]),
        sched=e["sched"])
    want = jax.eval_shape(eng.model.init_params, jax.random.PRNGKey(0))
    mine = arch.shapes(cfg["model"])
    tree = jax.tree_util
    if (tree.tree_structure(want) != tree.tree_structure(mine)
            or tree.tree_leaves(want) != tree.tree_leaves(mine)):
        raise RuntimeError("the engine's parameter layout differs from the "
                           f"one {cfg['arch']}'s plug-in makes")
    eng.params = None
    gc.collect()
    w = arch.make(cfg["model"], seed32)
    eng.params = w
    return eng, w


def instrument(eng, steps_state: Dict,
               note: Optional[Callable] = None) -> None:
    """Wrap the KV manager's calls in profiler annotations, time them on the
    host (ms per call name, for the step in ``steps_state["calls"]``), and
    note the live lengths each decode launch starts from, with what the
    plug-in's ``note(eng)``, where it has one, returns before the launch."""
    import jax
    kv = eng.kv

    def wrap(name: str, fn: Callable, before: Optional[Callable] = None):
        def call(*a, **k):
            if before is not None:
                before()
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(name):
                    return fn(*a, **k)
            finally:
                calls = steps_state.setdefault("calls", {})
                calls[name] = calls.get(name, 0.0) + 1e3 * (
                    time.perf_counter() - t0)
        return call

    def note_lengths():
        steps_state["decode"] = [int(x) for x in kv.lengths[kv.ready]]

    before = note_lengths
    if note is not None:
        def before():
            note_lengths()
            steps_state["note"] = note(eng)

    kv.begin = wrap("engine.kv_begin", kv.begin)
    kv.prefill_step = wrap("engine.prefill", kv.prefill_step)
    kv.reserve_decode = wrap("engine.reserve", kv.reserve_decode)
    kv.decode = wrap("engine.decode", kv.decode, before)
    kv.release = wrap("engine.release", kv.release)


# -- the loop -----------------------------------------------------------------
def unpaused(t: float, pauses: List[Tuple[float, float]]) -> float:
    """Wall time ``t`` less the pauses, (wall at end, seconds), before it."""
    return t - sum(d for end, d in pauses if end <= t)


@dataclasses.dataclass
class Req:
    uid: int
    due: float
    prompt: np.ndarray
    max_new: int
    ticket: Any = None
    first: Optional[float] = None
    last: Optional[float] = None
    admit: Optional[float] = None
    n_tokens: int = 0
    failed: bool = False


class Loop:
    """Drives ``submit`` and ``step`` and times every request on its own
    clock: the wall clock less the time spent in ``pause``."""

    def __init__(self, eng, spans: SpanLog, clock=time.perf_counter,
                 note: Optional[Callable] = None) -> None:
        self.eng = eng
        self.spans = spans
        self.wall = clock
        self.pauses: List[Tuple[float, float]] = []   # (wall at end, s)
        self.live: List[Req] = []
        self.steps: Dict[int, Dict] = {}
        self.state: Dict = {}
        self.i = 0
        self.on_step: Optional[Callable[[float], None]] = None
        # device memory after each step, to tell what a slow step did
        self.memory: Callable[[], Optional[Dict]] = lambda: None
        instrument(eng, self.state, note)

    def clock(self) -> float:
        return self.wall() - sum(d for _, d in self.pauses)

    def engine_time(self, t: float) -> float:
        """A time the engine stamped on the wall clock, on the loop's."""
        return unpaused(t, self.pauses)

    def pause(self, fn: Callable[[], None]) -> None:
        """Call ``fn`` with the loop's clock stopped.  Nothing is submitted
        or stepped meanwhile, so every request stands still with it."""
        t0 = self.wall()
        fn()
        t1 = self.wall()
        self.pauses.append((t1, t1 - t0))

    def submit(self, r: Req) -> None:
        import jax
        from repro.runtime.server import Request
        with jax.profiler.TraceAnnotation("bench.submit"):
            r.ticket = self.eng.submit(Request(uid=r.uid, prompt=r.prompt,
                                               max_new_tokens=r.max_new))
        self.live.append(r)

    def step(self) -> bool:
        import jax
        n0 = len(self.spans.events)
        self.state["decode"] = self.state["note"] = None
        self.state["calls"] = {}
        t0 = self.wall()
        with jax.profiler.TraceAnnotation("bench.step", i=self.i):
            busy = self.eng.step()
        wall_s = self.wall() - t0
        now = self.clock()
        new = self.spans.events[n0:]
        its = [m["active"] for m in new if m["span"] == "serve.decode_iter"]
        self.steps[self.i] = {
            "t": now, "wall_s": wall_s,
            "active": its[0] if its else self.eng.n_active,
            "iter_active": its[0] if its else None,
            "prefills": [(int(m["start"]), int(m["size"])) for m in new
                         if m["span"] != "serve.decode_iter"],
            "decode": self.state["decode"], "note": self.state["note"],
            "calls": self.state["calls"],
            "memory": self.memory()}
        self.i += 1
        self.observe(now)
        if self.on_step is not None:
            self.on_step(now)
        return busy

    def observe(self, now: float) -> None:
        keep = []
        for r in self.live:
            t = r.ticket
            n = len(t.tokens)
            if n and r.first is None:
                r.first = now
            if n != r.n_tokens:
                r.last, r.n_tokens = now, n
            if t.t_admit >= 0 and r.admit is None:
                r.admit = self.engine_time(t.t_admit)
            if t.finished:
                r.failed = t.status != "done"
            else:
                keep.append(r)
        self.live = keep

    def wait_until(self, t: float) -> None:
        import jax
        with jax.profiler.TraceAnnotation("bench.wait"):
            while self.clock() < t:
                time.sleep(min(0.002, max(0.0, t - self.clock())))

    def drain(self, reqs: List[Req]) -> None:
        """Step until every request in ``reqs`` is finished (set-up)."""
        for r in reqs:
            self.submit(r)
        while any(r.ticket.finished is False for r in reqs):
            self.step()


def as_reqs(items, base: float) -> List[Req]:
    return [Req(uid=it.uid, due=base + it.due, prompt=it.prompt,
                max_new=it.max_new) for it in items]


# -- one run ------------------------------------------------------------------
def execute(cell: Dict, seed: int, seconds: float, trace: bool,
            rate: Optional[float] = None, require_tpu: bool = True,
            fault: Optional[Callable] = None, control: bool = False
            ) -> Dict[str, Any]:
    """One run of ``cell``; returns the result line as a dict."""
    import jax
    if require_tpu:
        require_chips(cell["chips"])
        enable_cache()
    compiles: List[Tuple[float, str, float]] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, dur, **kw: compiles.append(
            (time.perf_counter(), name, dur))
        if name.startswith(COMPILE_PATH) else None)

    cfg, mix, arch = cell["config"], cell["mix"], cell["arch"]
    e = cfg["engine"]
    spans = SpanLog()
    eng, w = build_engine(cfg, arch, seed, spans)
    # The decode program reserves its temporaries when it is loaded: load
    # it first, with no other launch in flight.
    eng.kv.decode()
    if fault is not None:
        fault(eng)
    loop = Loop(eng, spans, note=getattr(arch, "note", None))
    if require_tpu:
        loop.memory = device_memory
    vocab = int(cfg["model"]["vocab_size"])
    sched = schedule.build(mix, vocab, seed, seconds, rate=rate)
    warm = schedule.warm_items(mix, sched, vocab, int(e["tokens_per_launch"]))
    loop.drain(as_reqs(warm, 0.0))
    if len(sched.prefixes):
        # again, now that the system prompts' pages are cached: the extend
        # launches that follow a prefix hit
        loop.drain(as_reqs(schedule.warm_items(
            mix, sched, vocab, int(e["tokens_per_launch"]), salt=1), 0.0))

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    traced: Dict[str, Any] = {"on": False, "done": False}
    # Set-up's objects live as long as the run: kept out of the collector's
    # full passes, so that none of those walks them inside the window.
    gc.collect()
    gc.freeze()
    gc_log: List[Tuple[float, float]] = []
    gc_timer = _gc_timer(gc_log)
    gc.callbacks.append(gc_timer)

    base_wall = time.perf_counter()
    base = loop.clock()
    if sched.kind == "open":
        base += 0.05
        reqs = as_reqs(sched.items, base)
        w0, w1 = base + sched.window[0], base + sched.window[1]
    else:
        reqs = as_reqs(sched.items, base)
        w0, w1 = base, base + seconds
    slice_a = w0 + 0.4 * (w1 - w0)
    slice_b = slice_a + min(4.0, 0.25 * (w1 - w0))

    def tracer(now: float) -> None:
        import xtrace
        idle = not traced["on"] and not traced["done"]
        if trace and idle and now >= slice_a:
            loop.pause(lambda: xtrace.start(tdir))
            traced["on"] = True
        elif traced["on"] and now >= slice_b:
            loop.pause(xtrace.stop)
            traced["on"], traced["done"] = False, True

    loop.on_step = tracer
    window = [r for r, it in zip(reqs, sched.items) if it.in_window]
    tok0 = 0
    step_w0 = None
    if sched.kind == "open":
        i = 0
        giveup = w1 + DRAIN_S
        while True:
            now = loop.clock()
            if step_w0 is None and now >= w0:
                step_w0, tok0 = loop.i, sum(r.n_tokens for r in reqs)
                kv0, db0 = dict(eng.kv.stats()), eng.tracker.count
            while i < len(reqs) and reqs[i].due <= now:
                loop.submit(reqs[i])
                i += 1
            if all(r.ticket is not None and r.ticket.finished for r in window):
                break
            if now > giveup:
                break
            if not loop.step():
                if i < len(reqs):
                    loop.wait_until(reqs[i].due)
                else:
                    break
        end = loop.clock()
        elapsed = w1 - w0
    else:
        kv0, db0 = dict(eng.kv.stats()), eng.tracker.count
        for r in reqs:
            loop.submit(r)
        step_w0 = loop.i
        while loop.clock() < w1:
            loop.step()
        end = loop.clock()
        elapsed = end - w0
        giveup = end
    if traced["on"]:
        import xtrace
        loop.pause(xtrace.stop)
        traced["on"] = False
    gc.callbacks.remove(gc_timer)
    gc.unfreeze()
    step_w1 = loop.i
    for r in window:
        if r.ticket is None or not r.ticket.finished:
            r.failed = True
    kv1 = dict(eng.kv.stats())
    out_tokens = sum(r.n_tokens for r in reqs) - tok0
    doorbells = eng.tracker.count - db0
    n_compiles = sum(1 for t, name, _ in compiles
                     if t >= base_wall and name == LOWER_EVENT)
    compile_s = sum(d for t, name, d in compiles if t >= base_wall
                    and not name.endswith("compile_time_saved_sec"))
    admitted = [r for r in reqs if r.admit is not None and r.admit >= w0
                and r.admit <= end]
    dev = device_info()

    reduced, trace_events = None, None
    if tdir is not None:
        import xtrace
        try:
            if traced["done"]:
                events = xtrace.load(tdir)
                trace_events = {k: len(v) for k, v in events.items()}
                reduced = xtrace.reduce(events, loop.steps)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)

    pauses = loop.pauses
    # the engine is freed before the reference runs
    done = [Served(r.uid, r.prompt, list(r.ticket.tokens), r.max_new)
            for r in (window if sched.kind == "open" else reqs)
            if r.ticket is not None and r.ticket.status == "done"]
    steps = loop.steps
    del eng, loop
    gc.collect()

    rng = np.random.default_rng(schedule.seed_words(seed) + [0xC4EC])
    picked = check.sample(done, rng, SAMPLE_TOKENS, SAMPLE_REQUESTS)
    t_ref = time.perf_counter()
    cmp = check.compare(arch.logits, w, cfg["model"], picked,
                        control=control)
    ref_s = time.perf_counter() - t_ref
    limit = float(cell["limits"]["max_logit_gap"])
    short = sum(1 for p in picked if len(p.tokens) != p.max_new)
    n_failed = sum(1 for r in (window if sched.kind == "open" else [])
                   if r.failed)
    # with ``control`` this is the control's gap, judged by the same test
    checks = {
        "max_logit_gap": {"value": cmp["max_logit_gap"], "limit": limit},
        "failed_requests": {"value": n_failed, "limit": 0},
        "short_outputs": {"value": short, "limit": 0},
    }
    correct = (cmp["max_logit_gap"] is not None
               and cmp["max_logit_gap"] <= limit and n_failed == 0
               and short == 0)

    run = _Record(
        arch=arch, model=cfg["model"], T=int(e["tokens_per_launch"]),
        slots=int(e["slots"]), peaks=peaks_for(dev["kind"], require_tpu),
        requests=window if sched.kind == "open" else [], giveup=end,
        elapsed=elapsed, out_tokens=out_tokens, doorbells=doorbells,
        compiles=n_compiles, setup_s=w0 - T_PROCESS,
        kv_delta={k: kv1[k] - kv0[k] for k in kv1
                  if isinstance(kv1[k], (int, float))
                  and not isinstance(kv1[k], bool)},
        prompt_tokens=sum(len(r.prompt) for r in admitted),
        steps=steps, window_steps=list(range(step_w0, step_w1)),
        trace=reduced)
    metrics = {}
    for m in (cell["per_layer"] if trace else cell["end_to_end"]):
        v = load_reader(m["name"])(run)
        if v is not None and np.isfinite(v):
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    if trace and reduced is not None:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": len(window) if sched.kind == "open" else len(reqs),
        "failed": n_failed, "metrics": metrics, "device": dev,
    }
    if trace and reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    walls = [steps[k]["wall_s"] for k in run.window_steps]
    slowest = max(run.window_steps, key=lambda k: steps[k]["wall_s"],
                  default=None)
    in_window = [d for t, d in gc_log if w0 <= unpaused(t, pauses) <= end]
    result["info"] = {
        "seed": seed, "control": control, "window_s": elapsed,
        "window_requests": len(window),
        "out_tokens": out_tokens, "reference_s": ref_s,
        "compiles_in_window": n_compiles,
        "compile_path_ms": 1e3 * compile_s, "trace_events": trace_events,
        "checked_requests": cmp["checked_requests"],
        "checked_tokens": cmp["checked_tokens"],
        "program_max_logit_gap": cmp["program_max_logit_gap"],
        # the latency tails in every run, also a traced one (its tracing
        # overhead), and what stalls the loop
        "ttft_p90_ms": _ms(readers.p90(readers.ttft_s(r, end)
                                       for r in run.requests)),
        "tpot_p90_ms": _ms(readers.p90(readers.tpot_s(r, end)
                                       for r in run.requests)),
        "late_submit_ms_max": 1e3 * max(
            [unpaused(r.ticket.t_submit, pauses) - r.due for r in reqs
             if r.ticket is not None] or [0.0]),
        "step_ms_median": _ms(float(np.median(walls)) if walls else None),
        "step_ms_max": _ms(max(walls) if walls else None),
        "slowest_step": None if slowest is None else dict(
            {k: steps[slowest][k] for k in ("active", "prefills", "calls",
                                            "memory")},
            at_s=steps[slowest]["t"] - w0,
            memory_before=steps.get(slowest - 1, {}).get("memory")),
        "gc_in_window": len(in_window),
        "gc_ms_max": _ms(max(in_window, default=0.0)),
        "paused_ms": 1e3 * sum(d for _, d in pauses),
    }
    result["checks"] = checks
    return result


def _ms(v: Optional[float]) -> Optional[float]:
    return None if v is None else 1e3 * v


def _gc_timer(out: List[Tuple[float, float]]) -> Callable:
    """A ``gc.callbacks`` entry appending (wall clock at the end, seconds)
    of each collection to ``out``."""
    t0 = [0.0]

    def timer(phase: str, info: Dict) -> None:
        if phase == "start":
            t0[0] = time.perf_counter()
        else:
            now = time.perf_counter()
            out.append((now, now - t0[0]))
    return timer


@dataclasses.dataclass
class _Record:
    arch: Any
    model: Dict
    T: int
    slots: int
    peaks: Dict
    requests: List[Req]
    giveup: float
    elapsed: float
    out_tokens: int
    doorbells: int
    compiles: int
    setup_s: float
    kv_delta: Dict
    prompt_tokens: int
    steps: Dict
    window_steps: List[int]
    trace: Optional[Dict]


@dataclasses.dataclass
class Served:
    """A finished request as the check sees it."""
    uid: int
    prompt: np.ndarray
    tokens: List[int]
    max_new: int


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="override the mix's arrival rate (rate sweeps)")
    ap.add_argument("--control", action="store_true",
                    help="judge the fp8 control's picks in place of the "
                         "served tokens (must come out not correct)")
    args = ap.parse_args(argv)
    try:
        cell = lookup(args.workload)
        res = execute(cell, args.seed, args.seconds, bool(args.trace),
                      rate=args.rate, control=args.control)
    except NoChip as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 3
    for k, c in res["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
