"""Paged KV manager, chunked prefill, and pluggable scheduling.

The serving memory-path invariants:

* equivalence — the engine (block tables, page pool, chunked prefill,
  prefix reuse) must produce *bit-identical* token streams to one-shot
  ``Server.serve`` of each request, the contiguous-cache oracle;
* capacity honesty — a too-small page pool evicts with
  ``reason="kv_pages"`` instead of corrupting neighbours;
* prefix sharing — prompts with a common prefix reuse the pages holding
  it, strictly shrinking the prefill command footprint;
* chunked prefill pacing — at most one bounded prefill launch is
  interleaved per decode iteration, so decode never stalls behind a long
  prompt.
"""
import threading
import time

import numpy as np
import pytest

from repro.configs import SMOKE_ARCHS
from repro.configs.shapes import SERVE_SHAPES, kv_geometry
from repro.core.session import SPAN_EVENT, TraceSession
from repro.runtime.scheduler import (AdmissionQueue, FairSharePolicy,
                                     PriorityPolicy, RequestTicket,
                                     make_policy)
from repro.runtime.server import ContinuousBatchingServer, Request, Server
from repro.runtime.traffic import TrafficSpec, generate, replay

CFG = SMOKE_ARCHS["gemma-2b"]

SPEC = TrafficSpec(n_requests=10, rate=1000.0, prompt_lens=(4, 8, 16),
                   new_tokens=(4, 9), seed=3)


class ListSink:
    def __init__(self):
        self.events = []

    def emit(self, event):
        self.events.append(event)


def _replay(sink=None, **kw):
    sess = TraceSession(name="test_kv", sinks=[sink] if sink else None)
    eng = ContinuousBatchingServer(CFG, batch_size=4, max_seq=64,
                                   tokens_per_launch=4, seed=0,
                                   session=sess, **kw)
    tickets, metrics = replay(eng, generate(SPEC, CFG.vocab_size),
                              realtime=False)
    return {t.uid: list(t.tokens) for t in tickets}, metrics, eng


def _oneshot(requests, max_seq=64):
    """Each request's tokens from one-shot ``Server.serve`` with the
    engine's weights (same seed): the contiguous-cache oracle."""
    solo = Server(CFG, batch_size=1, max_seq=max_seq, tokens_per_launch=1,
                  seed=0)
    out = {}
    for r in requests:
        ref = Request(r.uid, r.prompt, max_new_tokens=r.max_new_tokens)
        solo.serve([ref])
        out[r.uid] = ref.tokens
    return out


@pytest.fixture(scope="module")
def oneshot_ref():
    """One-shot tokens of every request in ``SPEC``."""
    return _oneshot([a.request for a in generate(SPEC, CFG.vocab_size)])


# -- geometry ---------------------------------------------------------------

def test_kv_geometry_and_serve_shapes():
    assert kv_geometry(64, 16, 4) == (4, 16)
    with pytest.raises(ValueError, match="multiple"):
        kv_geometry(64, 24, 4)
    with pytest.raises(ValueError, match="positive"):
        kv_geometry(64, 0, 4)
    for shape in SERVE_SHAPES.values():
        n_blk, pages = shape.geometry()
        assert n_blk * shape.kv_page_tokens == shape.max_seq
        assert pages == shape.slots * n_blk


def test_make_kv_rejects_unknown_backend():
    """The engine keeps its KV cache in pages: any other ``kv`` is
    refused before anything is built."""
    for kind in ("dense", "compressed"):
        with pytest.raises(ValueError, match="kv manager"):
            ContinuousBatchingServer(CFG, batch_size=1, max_seq=64, kv=kind)


@pytest.mark.parametrize("arch,serves", [
    ("mamba2-780m", True), ("jamba-v0.1-52b", True),
    ("whisper-medium", False),
], ids=["mamba2-780m", "jamba-v0.1-52b", "whisper-medium"])
def test_engine_rejects_model_without_extend(arch, serves):
    """A model without ``prefill_extend`` cannot fill pages, so the engine
    refuses it at construction; one-shot ``Server.serve`` still runs the
    recurrent and hybrid models (whose prefill takes the prompt only)."""
    cfg = SMOKE_ARCHS[arch]
    with pytest.raises(ValueError, match="prefill_extend"):
        ContinuousBatchingServer(cfg, batch_size=1, max_seq=32,
                                 tokens_per_launch=2, seed=0)
    if serves:
        srv = Server(cfg, batch_size=1, max_seq=32, tokens_per_launch=2,
                     seed=0)
        req = Request(0, np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
        srv.serve([req])
        assert len(req.tokens) == 3


# -- equivalence ------------------------------------------------------------

def test_paged_tokens_bit_identical_to_dense(oneshot_ref):
    """The engine's tokens equal one-shot serving's (a contiguous cache)."""
    toks, metrics, _ = _replay(kv_page_tokens=8)
    assert toks == oneshot_ref
    assert metrics["kv"]["backend"] == "paged"
    # default pool holds every slot fully grown: exhaustion impossible
    assert metrics["kv"]["pages_total"] == 4 * (64 // 8)
    assert metrics["evicted"] == 0


def test_chunked_prefill_bit_identical_both_backends(oneshot_ref):
    """Chunked prefill leaves the tokens of one-shot serving unchanged."""
    p_toks, p_m, _ = _replay(kv_page_tokens=8, prefill_chunk=4)
    assert p_toks == oneshot_ref
    # prompts longer than the chunk really went through the chunked path
    assert p_m["kv"]["chunked_prompts"] > 0
    assert p_m["kv"]["prefill_chunk_launches"] > 0


def test_launches_own_the_pools_they_are_given():
    """The extend and decode launches are given the page pools to own: the
    arrays passed in are deleted after each call (written in place, not
    copied whole), the manager holds the outputs, which stay live, and the
    doorbell still counts the pools among each launch's payload."""
    eng = ContinuousBatchingServer(CFG, batch_size=2, max_seq=64,
                                   tokens_per_launch=4, seed=0,
                                   kv_page_tokens=8)
    kv = eng.kv
    given = {"extend": [], "decode": []}

    def recorded(name, launch):
        def call(params, k_pool, v_pool, *rest):
            given[name].append((k_pool, v_pool))
            return launch(params, k_pool, v_pool, *rest)
        return call

    kv._extend = recorded("extend", kv._extend)
    kv._decode_slots = recorded("decode", kv._decode_slots)
    prompt = np.random.default_rng(1).integers(
        0, CFG.vocab_size, 12).astype(np.int32)
    tix = eng.submit(Request(0, prompt, max_new_tokens=6))
    eng.run(idle_timeout_s=0.0)
    assert given["extend"] and given["decode"]
    for k_pool, v_pool in given["extend"] + given["decode"]:
        assert k_pool.is_deleted() and v_pool.is_deleted()
    assert not kv.k_pool.is_deleted() and not kv.v_pool.is_deleted()
    n = eng.tracker.count_for("decode_slots")
    assert n == len(given["decode"])
    assert eng.tracker.total_payload("decode_slots") >= n * 2 * kv.k_pool.nbytes
    assert list(tix.tokens) == _oneshot([Request(0, prompt, 6)])[0]


# -- page exhaustion --------------------------------------------------------

def test_page_exhaustion_evicts_with_kv_pages_reason():
    eng = ContinuousBatchingServer(CFG, batch_size=4, max_seq=64,
                                   tokens_per_launch=2, seed=0,
                                   kv_page_tokens=8, kv_pages=9)
    rng = np.random.default_rng(0)
    tix = [eng.submit(Request(uid=i,
                              prompt=rng.integers(0, CFG.vocab_size, 20)
                              .astype(np.int32),
                              max_new_tokens=30)) for i in range(4)]
    eng.run(idle_timeout_s=0.0)
    assert all(t.finished for t in tix)
    evicted = [t for t in tix if t.status == "evicted"]
    assert evicted and all(t.reason == "kv_pages" for t in evicted)
    # survivors ran to their full budget untouched by the eviction
    assert any(t.status == "done" and len(t.tokens) == 30 for t in tix)


def test_pool_smaller_than_one_slot_rejected():
    with pytest.raises(ValueError, match="full slot"):
        ContinuousBatchingServer(CFG, batch_size=4, max_seq=64,
                                 tokens_per_launch=2, seed=0,
                                 kv_page_tokens=8, kv_pages=4)


def test_explicit_zero_page_tokens_rejected():
    # an explicit 0 must reach kv_geometry's validation, not silently
    # coerce to the default
    with pytest.raises(ValueError, match="positive"):
        ContinuousBatchingServer(CFG, batch_size=1, max_seq=64,
                                 tokens_per_launch=2, seed=0,
                                 kv_page_tokens=0)


# -- shared-prefix page reuse -----------------------------------------------

def _shared_prefix_requests(n=8, prefix_len=24, suffix_len=8, budget=6):
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, CFG.vocab_size, prefix_len).astype(np.int32)
    return [Request(uid=uid,
                    prompt=np.concatenate(
                        [prefix, rng.integers(0, CFG.vocab_size, suffix_len)
                         .astype(np.int32)]),
                    max_new_tokens=budget) for uid in range(n)]


def _run_shared(sink=None, **kw):
    sess = TraceSession(name="test_kv_shared",
                        sinks=[sink] if sink else None)
    eng = ContinuousBatchingServer(CFG, batch_size=4, max_seq=64,
                                   tokens_per_launch=2, seed=0,
                                   session=sess, **kw)
    tix = [eng.submit(r) for r in _shared_prefix_requests()]
    m = eng.run(idle_timeout_s=0.0)
    return {t.uid: list(t.tokens) for t in tix}, m


def test_shared_prefix_reuses_pages_and_shrinks_prefill():
    sink = ListSink()
    # 32-token pages hold no whole page of the 24-token prefix: no reuse
    d_toks, d_m = _run_shared(kv_page_tokens=32, prefill_chunk=8)
    p_toks, p_m = _run_shared(sink, kv_page_tokens=8, prefill_chunk=8)
    assert d_m["kv"]["prefix_hits"] == 0
    assert p_toks == d_toks                       # reuse never changes bits
    kv = p_m["kv"]
    assert kv["prefix_hits"] > 0
    assert kv["pages_reused"] > 0
    # strictly fewer prefill doorbells AND strictly fewer prefill payload
    # bytes than without reuse on the same workload
    assert kv["prefill_launches"] < d_m["kv"]["prefill_launches"]
    assert kv["prefill_payload_bytes"] < d_m["kv"]["prefill_payload_bytes"]
    names = [e.name for e in sink.events if e.kind == "progress"]
    assert names.count("kv.prefix_hit") == kv["prefix_hits"]
    assert "kv.alloc" in names and "kv.free" in names


def test_shared_prefix_pinned_under_pool_pressure():
    """Regression: shared prefix pages must be pinned *before* fresh pages
    are allocated.  A refcount-0 shared page sits in the reclaimable cache,
    and under pool pressure ``_take_pages`` used to reclaim it and hand it
    back as a prefill target for the very request attaching to it — the
    block table then held the same physical page twice and prefill clobbered
    the shared prefix."""
    eng = ContinuousBatchingServer(CFG, batch_size=1, max_seq=64,
                                   tokens_per_launch=2, seed=0,
                                   kv_page_tokens=8, kv_pages=8)
    rng = np.random.default_rng(7)
    prefix = rng.integers(0, CFG.vocab_size, 24).astype(np.int32)

    def mk(n):
        return rng.integers(0, CFG.vocab_size, n).astype(np.int32)

    # A fills the whole 8-page pool (4-page prompt + decode growth to the
    # cap), then releases: its 4 prompt pages stay cached, 4 go free
    eng.submit(Request(uid=0, prompt=np.concatenate([prefix, mk(8)]),
                       max_new_tokens=31))
    eng.run(idle_timeout_s=0.0)
    kv = eng.kv
    assert len(kv._cached) == 4 and len(kv._free) == 4

    # B shares the 3-page prefix and needs 5 fresh pages — one more than
    # the free list holds, forcing a reclaim from the cache while the
    # shared pages sit there at refcount 0
    b = RequestTicket(request=Request(
        uid=1, prompt=np.concatenate([prefix, mk(40)]), max_new_tokens=1))
    assert kv.begin(0, b)
    table = kv.tables[0, :int(kv.n_rows[0])].tolist()
    assert len(set(table)) == len(table)      # no physical page twice
    assert all(kv._ref[p] == 1 for p in table)
    assert kv.pages_reused == 3

    # rollback: with the free list exhausted and every reclaimable page
    # pinned as shared prefix, begin must fail AND undo its pins
    kv.release(0)
    kv._free.clear()
    c = RequestTicket(request=Request(
        uid=2, prompt=np.concatenate([prefix, mk(40)]), max_new_tokens=1))
    assert not kv.begin(0, c)
    assert len(kv._cached) == 3               # prefix pages reclaimable again
    assert all(kv._ref[p] == 0 for p in kv._cached)


def test_shared_prefix_under_pressure_tokens_match_dense():
    """End-to-end cover for the pin-before-allocate fix: prefix sharing and
    pool pressure *together* (each was covered separately before) must stay
    bit-identical to one-shot serving."""
    rng = np.random.default_rng(11)
    prefix = rng.integers(0, CFG.vocab_size, 24).astype(np.int32)

    def reqs():
        r = np.random.default_rng(13)

        def mk(n):
            return r.integers(0, CFG.vocab_size, n).astype(np.int32)

        return [Request(uid=0, prompt=np.concatenate([prefix, mk(8)]),
                        max_new_tokens=31),
                Request(uid=1, prompt=np.concatenate([prefix, mk(40)]),
                        max_new_tokens=1),
                Request(uid=2, prompt=np.concatenate([prefix, mk(8)]),
                        max_new_tokens=4)]

    eng = ContinuousBatchingServer(CFG, batch_size=1, max_seq=64,
                                   tokens_per_launch=2, seed=0,
                                   kv_page_tokens=8, kv_pages=8)
    tix = [eng.submit(r) for r in reqs()]
    eng.run(idle_timeout_s=0.0)
    assert {t.uid: list(t.tokens) for t in tix} == _oneshot(reqs())
    assert eng.kv.prefix_hits == 2            # both followers attached
    assert all(t.status in ("done",) for t in eng.tickets)


def test_no_registration_in_clamped_decode_write_zone():
    """Pages overlapping [max_seq - T, max_seq) are never registered for
    sharing: a slot finishing at the KV cap scatter-writes its clamped
    decode rows there, and registered pages must stay immutable once other
    requests attach (reachable with page_tokens < tokens_per_launch)."""
    rng = np.random.default_rng(21)
    base = rng.integers(0, CFG.vocab_size, 60).astype(np.int32)
    ext = rng.integers(0, CFG.vocab_size, 4).astype(np.int32)
    reqs = [Request(uid=0, prompt=base, max_new_tokens=5),
            Request(uid=1, prompt=np.concatenate([base, ext]),
                    max_new_tokens=1)]
    eng = ContinuousBatchingServer(CFG, batch_size=1, max_seq=64,
                                   tokens_per_launch=8, seed=0,
                                   kv_page_tokens=4)
    tix = [eng.submit(r) for r in reqs]
    eng.run(idle_timeout_s=0.0)
    assert {t.uid: list(t.tokens) for t in tix} == _oneshot(reqs)
    # A's 60-token prompt fully covers 15 pages, but page 14 spans
    # [56, 60) inside the clamp zone [56, 64) — only 14 get registered
    assert len(eng.kv._key_of) == 14
    # the follower still shares all 14 safe pages
    assert eng.kv.prefix_hits == 1 and eng.kv.pages_reused == 14


def test_traffic_prefix_len_prepends_shared_prefix():
    spec = TrafficSpec(n_requests=4, rate=100.0, prompt_lens=(4,),
                       new_tokens=(4,), seed=5, prefix_len=12)
    arrivals = generate(spec, vocab_size=CFG.vocab_size)
    prompts = [a.request.prompt for a in arrivals]
    assert all(len(p) == 16 for p in prompts)
    head = prompts[0][:12]
    assert all(np.array_equal(p[:12], head) for p in prompts)
    suffixes = {tuple(p[12:]) for p in prompts}
    assert len(suffixes) > 1                      # suffixes stay distinct


# -- chunked prefill pacing -------------------------------------------------

def test_chunked_prefill_bounds_launch_size_and_interleaves():
    sink = ListSink()
    sess = TraceSession(name="test_chunk", sinks=[sink])
    eng = ContinuousBatchingServer(CFG, batch_size=2, max_seq=64,
                                   tokens_per_launch=4, seed=0,
                                   session=sess, prefill_chunk=4)
    rng = np.random.default_rng(1)
    reqs = [Request(uid=i, prompt=rng.integers(0, CFG.vocab_size, 17)
                    .astype(np.int32), max_new_tokens=8) for i in range(3)]
    tix = [eng.submit(r) for r in reqs]
    eng.run(idle_timeout_s=0.0)
    assert all(t.status == "done" and len(t.tokens) == 8 for t in tix)
    spans = [e for e in sink.events if e.name == SPAN_EVENT]
    chunk_spans = [e for e in spans
                   if e.meta.get("span") == "serve.prefill_chunk"]
    assert chunk_spans, "chunked prompts must emit serve.prefill_chunk"
    assert all(e.meta["size"] <= 4 for e in chunk_spans)
    # per-prompt chunk count is ceil(17/4); launches are accounted on the
    # ticket so doorbell attribution still adds up per request
    assert all(t.n_prefill_launches == 5 for t in tix)
    # interleaving: between two chunk launches of the same prompt there is
    # at least one decode-iter span once any slot is decodable
    decode_ends = [e.t for e in spans
                   if e.meta.get("span") == "serve.decode_iter"]
    assert decode_ends, "decode proceeded while prompts were prefilling"


def test_chunked_prefill_keeps_decode_iters_flowing():
    """Span-order acceptance: while decode-ready work exists, no two chunk
    launches run without a decode iteration between them — a 32-token
    prompt joining the batch never stalls it.

    One long-budget request with a short (un-chunked) prompt pins slot 0
    so decode work is continuously present; chunked 32-token prompts
    stream through slot 1.  The order is checked only while the pinned
    decoder is active (once every slot is mid-prefill there is legitimately
    nothing to decode)."""
    sink = ListSink()
    sess = TraceSession(name="test_gap", sinks=[sink])
    eng = ContinuousBatchingServer(CFG, batch_size=2, max_seq=64,
                                   tokens_per_launch=4, seed=0,
                                   session=sess, prefill_chunk=4)
    rng = np.random.default_rng(2)

    def workload(uid0):
        pin = Request(uid=uid0, prompt=rng.integers(0, CFG.vocab_size, 4)
                      .astype(np.int32), max_new_tokens=60)
        chunked = [Request(uid=uid0 + 1 + i,
                           prompt=rng.integers(0, CFG.vocab_size, 32)
                           .astype(np.int32), max_new_tokens=12)
                   for i in range(2)]
        return [pin] + chunked

    tix = [eng.submit(r) for r in workload(100)]
    eng.run(idle_timeout_s=0.0)
    assert all(t.status == "done" for t in tix)
    names = [e.meta["span"] for e in sink.events if e.name == SPAN_EVENT
             and e.meta.get("span") in ("serve.prefill", "serve.prefill_chunk",
                                        "serve.decode_iter")]
    # the pinned prompt prefills first, then decodes its other 59 tokens, 4
    # a launch, in the next 15 decode iterations
    assert names[0] == "serve.prefill"
    iters = [i for i, n in enumerate(names) if n == "serve.decode_iter"]
    pinned = names[:iters[14] + 1]
    assert pinned.count("serve.prefill_chunk") >= 8   # chunks rode along
    pairs = set(zip(pinned, pinned[1:]))
    assert ("serve.prefill_chunk", "serve.prefill_chunk") not in pairs, (
        f"two chunk launches with no decode iteration between: {pinned}")


C = 8


@pytest.mark.parametrize("chunk,prompt_lens,launches", [
    (C, [3 * C], [(0, C), (C, C), (2 * C, C)]),
    (0, [3 * C], [(0, 3 * C)]),
    # the second prompt attaches to the first's 16-token page: its first
    # extend starts past the shared prefix
    (C, [3 * C, 4 * C],
     [(0, C), (C, C), (2 * C, C), (2 * C, C), (3 * C, C)]),
    (C, [2 * C + 3], [(0, C), (C, C), (2 * C, 3)]),
], ids=["paged-chunked", "paged-whole", "paged-prefix-hit",
        "paged-short-last-chunk"])
def test_extend_counters_match_closed_form(chunk, prompt_lens, launches):
    """kv.stats() and each prefill span count the query-key pairs an extend
    launch of C positions from ``start`` scores against an S-position cache
    (C·S) and those the causal mask keeps (C·start + C(C+1)/2), on a GQA
    model.  The prompts are prefixes of one token sequence, served one
    after another."""
    cfg = SMOKE_ARCHS["qwen3-8b"]
    S = 64
    sink = ListSink()
    eng = ContinuousBatchingServer(
        cfg, batch_size=2, max_seq=S, tokens_per_launch=4, seed=0,
        session=TraceSession(name="test_extend", sinks=[sink]),
        kv_page_tokens=16, prefill_chunk=chunk)
    tokens = np.random.default_rng(4).integers(
        0, cfg.vocab_size, max(prompt_lens)).astype(np.int32)
    for uid, n in enumerate(prompt_lens):
        tix = eng.submit(Request(uid=uid, prompt=tokens[:n],
                                 max_new_tokens=4))
        eng.run(idle_timeout_s=0.0)
        assert tix.status == "done"
    want = [(c * S, c * a + c * (c + 1) // 2) for a, c in launches]
    st = eng.kv.stats()
    assert (st["extend_scored_positions"], st["extend_live_positions"]) == (
        sum(w[0] for w in want), sum(w[1] for w in want))
    spans = [(e.meta["scored"], e.meta["live"]) for e in sink.events
             if e.name == SPAN_EVENT and e.meta.get("span") in (
                 "serve.prefill", "serve.prefill_chunk")
             and "scored" in e.meta]
    assert spans == want


# -- scheduling policies ----------------------------------------------------

def _tickets(*specs):
    """specs: (uid, priority, user, budget)."""
    out = []
    for uid, prio, user, budget in specs:
        r = Request(uid=uid, prompt=np.zeros(4, np.int32),
                    max_new_tokens=budget, priority=prio, user=user)
        out.append(RequestTicket(request=r))
    return out


def test_priority_policy_admits_highest_first():
    q = AdmissionQueue(max_pending=8)
    for t in _tickets((0, 0, "", 4), (1, 5, "", 4), (2, 5, "", 4),
                      (3, 1, "", 4)):
        q.submit(t)
    pol = PriorityPolicy()
    order = [q.pop(pol).uid for _ in range(4)]
    assert order == [1, 2, 3, 0]        # FIFO among the two priority-5s


def test_fair_share_policy_balances_users():
    q = AdmissionQueue(max_pending=8)
    for t in _tickets((0, 0, "a", 100), (1, 0, "a", 100),
                      (2, 0, "b", 1), (3, 0, "b", 1)):
        q.submit(t)
    pol = FairSharePolicy()
    order = [q.pop(pol).uid for _ in range(4)]
    # after user a's 100-token request, user b is least-served until its
    # cumulative budget catches up — so b gets both small requests next
    assert order == [0, 2, 3, 1]


def test_fair_share_reconciles_actual_tokens_on_finish():
    pol = FairSharePolicy()
    (t,) = _tickets((0, 0, "a", 100))
    pol.note_admitted(t)
    assert pol._served["a"] == 100      # budget charged up front
    t.tokens = [7, 7, 7]                # evicted after only 3 real tokens
    pol.note_finished(t)
    assert pol._served["a"] == 3        # reconciled to actual usage
    (u,) = _tickets((1, 0, "b", 5))
    pol.note_finished(u)                # never admitted: no-op
    assert "b" not in pol._served


def test_fair_share_ledger_bounded():
    pol = FairSharePolicy(max_users=2)
    for t in _tickets(*[(i, 0, f"u{i}", 1) for i in range(5)]):
        pol.note_admitted(t)
        t.tokens = [1]
        pol.note_finished(t)
    assert len(pol._served) <= 2        # churny users don't grow state
    assert not pol._inflight


def test_make_policy_names_and_unknown():
    for name in ("fifo", "priority", "fair"):
        assert make_policy(name).name == name
    with pytest.raises(ValueError, match="policy"):
        make_policy("sjf")


def test_peek_matches_pop_and_keeps_queue():
    q = AdmissionQueue(max_pending=8)
    for t in _tickets((0, 0, "", 4), (1, 7, "", 4)):
        q.submit(t)
    pol = PriorityPolicy()
    assert q.peek(pol).uid == 1
    assert len(q) == 2                  # peek never removes
    assert q.pop(pol).uid == 1
    assert q.peek().uid == 0            # default FIFO peek


def test_pop_policy_keeps_drop_oldest_semantics():
    q = AdmissionQueue(max_pending=2, policy="drop_oldest")
    ts = _tickets((0, 9, "", 4), (1, 0, "", 4), (2, 0, "", 4))
    q.submit(ts[0])
    q.submit(ts[1])
    ok, dropped = q.submit(ts[2])
    assert ok and dropped is ts[0]      # overflow drops the OLDEST queued
    assert q.n_dropped == 1             # regardless of its priority
    assert q.pop(PriorityPolicy()).uid == 1


def test_engine_priority_scheduling_end_to_end():
    eng = ContinuousBatchingServer(CFG, batch_size=1, max_seq=32,
                                   tokens_per_launch=2, seed=0,
                                   sched="priority")
    rng = np.random.default_rng(3)
    reqs = [Request(uid=i, prompt=rng.integers(0, CFG.vocab_size, 4)
                    .astype(np.int32), max_new_tokens=3, priority=i)
            for i in range(4)]
    tix = [eng.submit(r) for r in reqs]
    eng.run(idle_timeout_s=0.0)
    admits = sorted(tix, key=lambda t: t.t_admit)
    assert [t.uid for t in admits] == [3, 2, 1, 0]


# -- admission-queue condition variable -------------------------------------

def test_wait_for_work_wakes_on_submit():
    q = AdmissionQueue(max_pending=4)
    (t,) = _tickets((0, 0, "", 4))

    def late_submit():
        time.sleep(0.05)
        q.submit(t)

    threading.Thread(target=late_submit, daemon=True).start()
    t0 = time.perf_counter()
    assert q.wait_for_work(timeout=5.0)
    assert time.perf_counter() - t0 < 2.0   # woke on notify, not timeout


def test_wait_for_work_times_out_empty():
    q = AdmissionQueue(max_pending=4)
    t0 = time.perf_counter()
    assert not q.wait_for_work(timeout=0.05)
    assert time.perf_counter() - t0 >= 0.04


def test_wait_for_work_wakes_on_close():
    q = AdmissionQueue(max_pending=4)

    def late_close():
        time.sleep(0.05)
        q.close()

    threading.Thread(target=late_close, daemon=True).start()
    assert q.wait_for_work(timeout=5.0)
    assert q.closed
