"""Serving runtime: batched prefill + decode with submission accounting.

Decode is the pathological small-submission regime the paper's DMA study
targets: one token of useful work per dispatch.  The server therefore
exposes ``tokens_per_launch`` (multi-token graph launch — scan T decode
steps into one dispatch) and tracks doorbells so the benefit is measurable.

Two serving surfaces share one model/params/session:

* :class:`Server.serve` — one-shot: a static batch decodes to completion.
* :class:`ContinuousBatchingServer` — a request queue with admission
  control and eviction, per-request KV slots, and a decode loop that new
  requests *join while it runs* (and leave mid-stream) without ever
  recompiling the graph-launched multi-token decode.

The continuous engine keeps one decode state **per slot** (each slot is a
full batch-1 state pytree, stacked on a fresh leading axis and driven by a
``jax.vmap`` over slots).  Each slot therefore carries its own cache length
and its own greedy chain — a request's tokens are *independent of batch
composition and join time*, which is what makes continuous-batching output
exactly equal to a one-shot ``serve()`` of the same request.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..core.session import SpanHandle, TraceSession
from ..models import get_model
from .scheduler import (AdmissionQueue, RequestTicket, latency_stats,
                        make_policy)

__all__ = ["Server", "Request", "ContinuousBatchingServer"]


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray          # [S] int32
    max_new_tokens: int = 16
    tokens: Optional[List[int]] = None
    priority: int = 0           # PriorityPolicy: higher admits first
    user: str = ""              # FairSharePolicy: least-served user first


def _empty_metrics() -> Dict[str, Any]:
    return {"wall_s": 0.0, "doorbells": 0, "new_tokens": 0,
            "tokens_per_doorbell": 0.0, "trace_events": 0}


class Server:
    def __init__(self, cfg: ModelConfig, batch_size: int, max_seq: int,
                 tokens_per_launch: Optional[int] = None, seed: int = 0,
                 session: Optional[TraceSession] = None) -> None:
        self.cfg = cfg
        self.B = batch_size
        self.max_seq = max_seq
        # ``tokens_per_launch=None`` -> auto-apply the tuned policy for this
        # (model config, platform, device count), if one is persisted; an
        # explicit value always wins (repro.tune is the tuner that writes
        # these policies).
        self.policy = None
        if tokens_per_launch is None:
            from ..tune.policy import load_policy_for
            self.policy = load_policy_for(cfg)
            tokens_per_launch = (self.policy.knob("tokens_per_launch", 1)
                                 if self.policy else 1)
        self.T = max(1, int(tokens_per_launch))
        self.model = get_model(cfg)
        # Shared timeline: pass a session to merge serving events with a
        # trainer's or a benchmark's; otherwise the server owns one.
        self.session = session or TraceSession(name="server")
        self.tracker = self.session.doorbell
        # jitted: the compiled init writes bf16 weights directly, where an
        # eager init holds a float32 temporary per weight
        self.params = jax.jit(self.model.init_params)(jax.random.PRNGKey(seed))

        self._prefill = self.tracker.wrap(
            jax.jit(lambda p, toks: self.model.prefill(p, toks, max_seq)),
            "prefill")

        if self.T == 1:
            self._decode = self.tracker.wrap(
                jax.jit(self.model.decode_step), "decode_step")
        else:
            def decode_T(params, state, tokens):
                def body(carry, _):
                    st, tok = carry
                    st, logits = self.model.decode_step(params, st, tok)
                    nxt = jnp.argmax(logits[:, -1:, :], axis=-1).astype(
                        tok.dtype)
                    return (st, nxt), nxt[:, 0]
                (state, _), toks = jax.lax.scan(
                    body, (state, tokens), None, length=self.T)
                return state, toks  # [T, B]

            self._decode_T = self.tracker.wrap(jax.jit(decode_T),
                                               "decode_T_steps")

    def _decode_block(self, state, nxt, want: int
                      ) -> Tuple[Any, List[jax.Array], jax.Array]:
        """One multi-token graph launch; keep only ``want`` tokens.

        The launch always scans ``self.T`` steps; when ``want < T`` the
        block is truncated and only the prefix is useful output.  Returns
        ``(state, tokens, continuation)`` where ``continuation`` is the
        last *kept* token (``tok_block[take - 1]``, not ``tok_block[-1]``
        — a truncated block's final token is past the useful prefix, so a
        re-entered decode loop must not continue from it).
        """
        state, tok_block = self._decode_T(self.params, state, nxt)
        take = min(self.T, want)
        toks = [tok_block[t] for t in range(take)]
        nxt = tok_block[take - 1][:, None].astype(jnp.int32)
        return state, toks, nxt

    def serve(self, requests: List[Request]) -> Dict[str, Any]:
        """Greedy-decode a batch of requests (padded to server batch)."""
        if not requests:
            return _empty_metrics()
        if len(requests) > self.B:
            raise ValueError(
                f"got {len(requests)} requests for batch_size={self.B}; "
                f"use ContinuousBatchingServer for queued admission")
        for r in requests:
            if len(r.prompt) > self.max_seq:
                raise ValueError(
                    f"request {r.uid}: prompt length {len(r.prompt)} exceeds "
                    f"max_seq={self.max_seq}; the decode state would overrun")
        S = max(len(r.prompt) for r in requests)
        toks = np.zeros((self.B, S), np.int32)
        for i, r in enumerate(requests):
            toks[i, S - len(r.prompt):] = r.prompt      # left-pad
        t0 = time.perf_counter()
        # session may be shared with other consumers: report per-run deltas
        db0 = self.tracker.count
        ev0 = self.session.n_events
        max_new = max(r.max_new_tokens for r in requests)
        with self.session.span("serve.oneshot", batch=len(requests),
                               max_new=max_new):
            with self.session.span("serve.prefill", seq_len=S):
                state, logits = self._prefill(self.params, jnp.asarray(toks))
            nxt = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
            out = [nxt[:, 0]]
            produced = 1
            while produced < max_new:
                with self.session.span("serve.decode_iter",
                                       produced=produced):
                    if self.T == 1:
                        state, logits = self._decode(self.params, state, nxt)
                        nxt = jnp.argmax(logits[:, -1:, :],
                                         axis=-1).astype(jnp.int32)
                        out.append(nxt[:, 0])
                        produced += 1
                    else:
                        state, block, nxt = self._decode_block(
                            state, nxt, max_new - produced)
                        out.extend(block)
                        produced += len(block)
            jax.block_until_ready(out[-1])
        wall = time.perf_counter() - t0
        tokens = np.stack([np.asarray(t) for t in out], axis=1)  # [B, new]
        for i, r in enumerate(requests):
            r.tokens = tokens[i, :r.max_new_tokens].tolist()
        doorbells = self.tracker.count - db0
        # useful tokens = what each request asked for, NOT max_new * B:
        # heterogeneous requests decode to the batch max but only keep their
        # own budget, and the tuner's objective reads exactly these fields.
        new_tokens = int(sum(r.max_new_tokens for r in requests))
        return {
            "wall_s": wall,
            "doorbells": doorbells,
            "new_tokens": new_tokens,
            "tokens_per_doorbell": new_tokens / max(1, doorbells),
            "trace_events": self.session.n_events - ev0,
        }


class ContinuousBatchingServer(Server):
    """Continuous-batching inference engine on top of :class:`Server`.

    Requests are :meth:`submit`-ted (thread-safe — a traffic-generator
    thread can feed a running decode loop) into a bounded
    :class:`~repro.runtime.scheduler.AdmissionQueue`; :meth:`run` drives
    the decode loop, admitting queued requests into free KV slots *between
    decode launches* so the jitted, graph-launched ``tokens_per_launch``
    decode never changes shape (and never recompiles) across join/leave
    boundaries.

    Per-request state: slot ``i`` holds a complete batch-1 decode-state
    pytree (own KV cache, own cache ``length``); the engine stacks all
    ``batch_size`` slot states on a new leading axis and decodes them with
    one ``vmap``-ed launch.  Prefill runs per admitted request at its exact
    prompt length (compiled once per distinct length), so a request's
    greedy chain is bit-identical to ``Server.serve([request])`` no matter
    when it joined or who shared the batch.

    Lifecycle events land on the session timeline as ``progress`` events
    (``serve.submit/admit/finish/evict/reject``); a finish event carries
    the emitted tokens as its payload (4 bytes each), so token throughput
    is recoverable from session accounting alone.

    Observability plane: the engine installs a
    :class:`~repro.obs.LiveSummary` sink on its session, so
    :meth:`live_summary` answers at any point *during* a run with the same
    schema ``session.summary()`` gives post-mortem (plus engine state:
    active slots, queue depth, ticket fates).  :meth:`start_live_endpoint`
    serves that over HTTP (``GET /summary``, ``GET /stream``) — the
    loadtest harness exposes it with ``--live``.
    """

    def __init__(self, cfg: ModelConfig, batch_size: int, max_seq: int,
                 tokens_per_launch: Optional[int] = None, seed: int = 0,
                 session: Optional[TraceSession] = None,
                 max_pending: int = 256,
                 admission: str = "reject",
                 kv: str = "dense",
                 kv_page_tokens: Optional[int] = None,
                 kv_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 sched: str = "fifo") -> None:
        super().__init__(cfg, batch_size, max_seq,
                         tokens_per_launch=tokens_per_launch, seed=seed,
                         session=session)
        self.queue = AdmissionQueue(max_pending=max_pending, policy=admission)
        self.sched_policy = make_policy(sched)
        self.tickets: List[RequestTicket] = []      # submit order, all fates
        self._slot_tix: List[Optional[RequestTicket]] = [None] * self.B
        self._prefilling: set = set()               # slots mid-chunked-prefill
        self._prefill_rr = 0                        # round-robin tick cursor
        # per-request causal spans: a request's lifetime crosses scheduler
        # iterations (and the decode launch is shared by every active slot),
        # so these are manual handles closed in _finish with *declared*
        # attribution — n_launches decode launches + prefill launches
        self._req_spans: Dict[int, SpanHandle] = {}

        # live observability plane: every event the (possibly shared)
        # session emits while this engine exists also folds into an
        # incremental summary a poller can read mid-run
        from ..obs.live import LiveSummary
        self.live = LiveSummary(name=self.session.name)
        self.session.add_sink(self.live)
        self._live_server: Optional[Any] = None

        # KV backend: dense (stacked per-slot states, the PR-7 layout) or
        # paged (global page pool + block tables + shared-prefix reuse).
        # Unset knobs fall back to the tuned policy for this config.
        if kv == "paged" and kv_page_tokens is None:
            kv_page_tokens = int(self.policy.knob("kv_page_tokens", 16)
                                 if self.policy else 16)
        if prefill_chunk is None:
            prefill_chunk = int(self.policy.knob("prefill_chunk", 0)
                                if self.policy else 0)
        from .kv import make_kv
        # None -> default; explicit invalid values (e.g. 0) must reach
        # kv_geometry's validation instead of being silently coerced
        self.kv = make_kv(
            self, kv,
            page_tokens=16 if kv_page_tokens is None else kv_page_tokens,
            pages=kv_pages, prefill_chunk=prefill_chunk)

    @property
    def _decode_slots(self):
        """The backend's vmapped decode launch (tests inspect its compile
        cache to prove shape stability across churn)."""
        return self.kv._decode_slots

    # -- intake (any thread) ----------------------------------------------
    def submit(self, request: Request) -> RequestTicket:
        """Enqueue a request; returns its ticket (possibly already
        ``rejected`` — admission control, not an exception, because the
        traffic thread must keep running)."""
        tix = RequestTicket(request=request, t_submit=time.perf_counter())
        if len(request.prompt) > self.max_seq:
            tix.status, tix.reason = "rejected", "prompt_exceeds_max_seq"
            tix.t_done = tix.t_submit
        else:
            accepted, dropped = self.queue.submit(tix)
            if dropped is not None:
                dropped.status, dropped.reason = "evicted", "queue_overflow"
                dropped.t_done = time.perf_counter()
                self.session.emit("progress", "serve.evict",
                                  uid=dropped.uid, reason=dropped.reason)
                self._end_request_span(dropped)
            if not accepted:
                tix.status = "rejected"
                tix.reason = ("intake_closed" if self.queue.closed
                              else "queue_full")
                tix.t_done = time.perf_counter()
            else:
                self._req_spans[tix.uid] = self.session.start_span(
                    "serve.request", uid=tix.uid,
                    prompt_len=int(len(request.prompt)))
        self.tickets.append(tix)
        name = "serve.submit" if not tix.finished else "serve.reject"
        self.session.emit("progress", name, uid=tix.uid, status=tix.status,
                          reason=tix.reason)
        return tix

    def close_intake(self) -> None:
        """No more submits: :meth:`run` may exit once everything drains."""
        self.queue.close()

    # -- live observability (any thread) -----------------------------------
    def live_summary(self) -> Dict[str, Any]:
        """Session-schema summary *now*, plus engine state.

        Safe from any thread while the decode loop runs; this is the
        poll-mode payload of the live endpoint.
        """
        snap = self.live.snapshot()
        tickets = list(self.tickets)
        snap["engine"] = {
            "slots": self.B,
            "active": self.n_active,
            "queued": len(self.queue),
            "intake_closed": self.queue.closed,
            "tickets": {s: sum(1 for t in tickets if t.status == s)
                        for s in ("queued", "active", "done", "evicted",
                                  "rejected")},
            "tokens_emitted": sum(len(t.tokens) for t in tickets),
        }
        return snap

    def start_live_endpoint(self, port: int = 0, host: str = "127.0.0.1"):
        """Serve :meth:`live_summary` over HTTP; returns the started
        :class:`~repro.obs.LiveServer` (``.url``, ``.stop()``)."""
        from ..obs.live import LiveServer
        self._live_server = LiveServer(self.live_summary, host=host,
                                       port=port).start()
        return self._live_server

    def stop_live_endpoint(self) -> None:
        if self._live_server is not None:
            self._live_server.stop()
            self._live_server = None

    # -- scheduling (decode-loop thread) -----------------------------------
    def _free_slots(self) -> List[int]:
        return [i for i, t in enumerate(self._slot_tix) if t is None]

    @property
    def n_active(self) -> int:
        return sum(1 for t in self._slot_tix if t is not None)

    def _end_request_span(self, tix: RequestTicket) -> None:
        """Close a request's causal span with its declared attribution.

        The vmapped decode launch is shared by every active slot, so this
        request's share of the command stream can't be read off stamped
        events — it is *declared* here instead: one doorbell per decode
        launch the request rode (``n_launches``) plus its prefill, and
        4 bytes per emitted token (matching the finish-event payload).
        """
        handle = self._req_spans.pop(tix.uid, None)
        if handle is None:
            return
        launches = tix.n_launches
        handle.end(uid=tix.uid, status=tix.status, slot=tix.slot,
                   n_tokens=len(tix.tokens),
                   doorbells=launches + tix.n_prefill_launches,
                   graph_launches=launches,
                   payload=4 * len(tix.tokens))

    def _on_first_token(self, tix: RequestTicket, tok0: int) -> None:
        """Prefill completed: record token 0, finish degenerate requests."""
        self._prefilling.discard(tix.slot)
        tix.tokens.append(tok0)
        tix.t_first = time.perf_counter()
        if len(tix.tokens) >= min(tix.request.max_new_tokens, tix.cap):
            self._finish(tix)           # degenerate 1-token request

    def _admit(self) -> int:
        """Move queued tickets into free slots.

        Whole-prompt admission (no chunking) prefills synchronously here —
        the pre-refactor behavior.  Prompts longer than the backend's
        ``prefill_chunk`` only *start* here; :meth:`_prefill_tick` advances
        them one bounded launch per scheduler iteration so active slots
        keep decoding underneath.
        """
        admitted = 0
        for slot in self._free_slots():
            tix = self.queue.pop(self.sched_policy)
            if tix is None:
                break
            r = tix.request
            if not self.kv.begin(slot, tix):
                # page pool exhausted even after reclaiming shared pages
                tix.status, tix.reason = "evicted", "kv_pages"
                tix.t_done = time.perf_counter()
                self.session.emit("progress", "serve.evict", uid=tix.uid,
                                  reason=tix.reason)
                self._end_request_span(tix)
                self.sched_policy.note_finished(tix)
                continue
            tix.status, tix.slot = "active", slot
            tix.t_admit = time.perf_counter()
            # KV capacity: decode token j (0-based; token 0 comes straight
            # from prefill logits) writes cache position prompt_len + j - 1,
            # which must stay below max_seq.
            tix.cap = self.max_seq - len(r.prompt) + 1
            self._slot_tix[slot] = tix
            self._prefilling.add(slot)
            chunk = self.kv.chunk
            if not (chunk and len(r.prompt) > chunk):
                tok0 = self.kv.prefill_step(slot)   # one whole-prompt launch
                self.session.emit("progress", "serve.admit", uid=tix.uid,
                                  slot=slot,
                                  queued_s=tix.t_admit - tix.t_submit)
                self._on_first_token(tix, tok0)
            else:
                self.session.emit("progress", "serve.admit", uid=tix.uid,
                                  slot=slot,
                                  queued_s=tix.t_admit - tix.t_submit)
            admitted += 1
        return admitted

    def _prefill_tick(self) -> None:
        """Advance at most ONE pending chunked prefill by one launch.

        One bounded launch per scheduler iteration keeps the decode-iter
        gap under control (the acceptance bar: no gap beyond 2x the median
        decode-iter duration); round-robin across prefilling slots keeps
        long prompts from starving each other.
        """
        pending = sorted(s for s in self._prefilling
                         if self._slot_tix[s] is not None)
        if not pending:
            return
        slot = pending[self._prefill_rr % len(pending)]
        self._prefill_rr += 1
        tok0 = self.kv.prefill_step(slot)
        if tok0 is not None:
            self._on_first_token(self._slot_tix[slot], tok0)

    def _finish(self, tix: RequestTicket, reason: Optional[str] = None
                ) -> None:
        evicted = (reason is not None
                   or len(tix.tokens) < tix.request.max_new_tokens)
        tix.status = "evicted" if evicted else "done"
        if evicted:
            tix.reason = reason or "kv_overrun"
        tix.t_done = time.perf_counter()
        tix.request.tokens = list(tix.tokens)
        self._slot_tix[tix.slot] = None
        self._prefilling.discard(tix.slot)
        self.kv.release(tix.slot)
        self.session.emit(
            "progress", "serve.evict" if evicted else "serve.finish",
            payload_bytes=4 * len(tix.tokens), uid=tix.uid, slot=tix.slot,
            tokens=len(tix.tokens), latency_s=tix.latency_s,
            **({"reason": tix.reason} if evicted else {}))
        self._end_request_span(tix)
        self.sched_policy.note_finished(tix)

    def step(self) -> bool:
        """One scheduler iteration: admit, advance one chunked prefill,
        then one decode launch across all decodable slots; harvest per-slot
        tokens.  Returns False if idle."""
        self._admit()
        self._prefill_tick()
        decodable = [slot for slot, tix in enumerate(self._slot_tix)
                     if tix is not None and slot not in self._prefilling]
        if not decodable:
            return self.n_active > 0    # prefills pending still count
        # paged backend: grow block tables for the coming T writes; slots
        # the pool cannot serve are evicted (reason="kv_pages") and their
        # freed pages immediately retried for the survivors
        while True:
            victims = self.kv.reserve_decode(decodable)
            if not victims:
                break
            for slot in victims:
                self._finish(self._slot_tix[slot], reason="kv_pages")
                decodable.remove(slot)
            if not decodable:
                return self.n_active > 0
        with self.session.span("serve.decode_iter", active=self.n_active):
            blocks = self.kv.decode()               # [B, T] host sync
            for slot in decodable:
                tix = self._slot_tix[slot]
                tix.n_launches += 1
                budget = min(tix.request.max_new_tokens, tix.cap)
                take = min(self.T, budget - len(tix.tokens))
                tix.tokens.extend(int(t) for t in blocks[slot, :take])
                if len(tix.tokens) >= budget:
                    self._finish(tix)
        return True

    def run(self, idle_timeout_s: float = 5.0,
            poll_s: float = 0.0005) -> Dict[str, Any]:
        """Drive the decode loop until all work drains.

        Exits when no request is queued or active AND either the intake is
        closed (threaded replay calls :meth:`close_intake` when the
        producer finishes) or nothing has arrived for ``idle_timeout_s``
        (synchronous submit-then-run callers never close the intake).
        When idle, the loop blocks on the queue's condition variable —
        :meth:`submit` and :meth:`close_intake` wake it immediately —
        with ``poll_s`` as the floor fallback timeout instead of the old
        ``sleep(poll_s)`` spin.  Returns run metrics; per-request detail
        lives on the tickets.
        """
        t0 = time.perf_counter()
        db0, ev0 = self.tracker.count, self.session.n_events
        # snapshot: the tickets list grows from the traffic thread mid-run
        done0 = sum(1 for t in list(self.tickets) if t.t_done >= 0)
        tok0 = sum(len(t.tokens) for t in list(self.tickets))
        idle_since: Optional[float] = None
        while True:
            if self.step():
                idle_since = None
                continue
            if len(self.queue) == 0:
                if self.queue.closed:
                    break
                now = time.perf_counter()
                idle_since = idle_since if idle_since is not None else now
                remaining = idle_timeout_s - (now - idle_since)
                if remaining <= 0:
                    break
                self.queue.wait_for_work(timeout=max(poll_s, remaining))
            else:
                # queued work raced in after this iteration's admit pass;
                # loop around immediately
                continue
        wall = time.perf_counter() - t0
        tickets = list(self.tickets)
        ended = [t for t in tickets if t.t_done >= t0]
        by_status = {s: sum(1 for t in ended if t.status == s)
                     for s in ("done", "evicted", "rejected")}
        new_tokens = sum(len(t.tokens) for t in tickets) - tok0
        doorbells = self.tracker.count - db0
        out = {
            "wall_s": wall,
            "requests": sum(1 for t in tickets if t.t_done >= 0) - done0,
            "completed": by_status["done"],
            "evicted": by_status["evicted"],
            "rejected": by_status["rejected"],
            "new_tokens": int(new_tokens),
            "doorbells": doorbells,
            "tokens_per_doorbell": new_tokens / max(1, doorbells),
            "tokens_per_s": new_tokens / max(wall, 1e-9),
            "trace_events": self.session.n_events - ev0,
            # backend memory-path accounting (pages, prefix hits, prefill
            # launches/bytes) — engine-lifetime totals, not per-run deltas
            "kv": self.kv.stats(),
        }
        # latency percentiles over requests that actually decoded; instant
        # rejections would skew p50 toward zero
        out.update(latency_stats(
            [t for t in ended if t.status in ("done", "evicted")]))
        return out
