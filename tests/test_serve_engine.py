"""Continuous-batching engine: equivalence, admission/eviction, traffic.

The load-bearing invariant: because every KV slot carries its own block
table, cache length and greedy chain, a request's tokens are independent of
batch composition and join time — continuous batching must produce
*exactly* the tokens a one-shot ``serve()`` of the same request would.
"""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKE_ARCHS
from repro.runtime.scheduler import (AdmissionQueue, RequestTicket,
                                     percentile)
from repro.runtime.server import ContinuousBatchingServer, Request, Server
from repro.runtime.traffic import TrafficSpec, generate, replay

CFG = SMOKE_ARCHS["gemma-2b"]


def mk_request(uid, plen, budget, seed=7):
    rng = np.random.default_rng(seed + uid)
    return Request(uid, rng.integers(0, CFG.vocab_size,
                                     size=plen).astype(np.int32),
                   max_new_tokens=budget)


@pytest.fixture(scope="module")
def engine():
    return ContinuousBatchingServer(CFG, batch_size=2, max_seq=32,
                                    tokens_per_launch=3, seed=1,
                                    max_pending=8)


@pytest.fixture(scope="module")
def solo():
    """One-shot single-request reference decoder (same params: same seed)."""
    return Server(CFG, batch_size=1, max_seq=32, tokens_per_launch=1, seed=1)


# -- serve() bugfixes -------------------------------------------------------

def test_serve_empty_batch_returns_wellformed_metrics(solo):
    out = solo.serve([])
    assert out == {"wall_s": 0.0, "doorbells": 0, "new_tokens": 0,
                   "tokens_per_doorbell": 0.0, "trace_events": 0}


def test_serve_overfull_batch_raises_valueerror_not_assert(solo):
    reqs = [mk_request(i, 4, 2) for i in range(2)]    # batch_size is 1
    with pytest.raises(ValueError, match="batch_size"):
        solo.serve(reqs)


def test_decode_block_truncated_continuation_token(solo):
    """Regression: a truncated block (want < T) must hand back the last
    *kept* token ``tok_block[take-1]`` as its continuation, not
    ``tok_block[-1]`` — the scanned-past token belongs to a speculative
    suffix the caller never accepted, so any downstream use of the
    continuation (streaming, stop-sequence checks) would fork the chain."""
    srv = Server(CFG, batch_size=1, max_seq=32, tokens_per_launch=3, seed=1)
    # this uid/plen is chosen so the scanned-past token differs *by value*
    # from the last kept one — a degenerate constant greedy chain (most
    # random prompts on the smoke config) would mask the bug
    r = mk_request(9, 4, 1)
    toks = np.asarray(r.prompt)[None, :]
    state, logits = srv._prefill(srv.params, jnp.asarray(toks))
    nxt = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
    first = int(nxt[0, 0])
    state, block, nxt = srv._decode_block(state, nxt, want=2)
    assert len(block) == 2
    # continuation == last kept token, not the scanned-past one
    assert int(nxt[0, 0]) == int(block[-1][0])
    # and the kept prefix is the exact uninterrupted greedy chain
    ref = mk_request(9, 4, 3)          # same uid/seed -> same prompt
    solo.serve([ref])
    assert [first] + [int(b[0]) for b in block] == ref.tokens


# -- continuous batching ----------------------------------------------------

def test_continuous_tokens_equal_oneshot_per_request(engine, solo):
    """5 requests through 2 slots: joins/leaves mid-decode, heterogeneous
    prompt lengths and budgets — every token stream identical to a solo
    one-shot serve of the same request."""
    shapes = [(3, 4), (5, 7), (8, 5), (3, 7), (5, 4)]
    reqs = [mk_request(i, p, b) for i, (p, b) in enumerate(shapes)]
    tickets = [engine.submit(r) for r in reqs]
    out = engine.run(idle_timeout_s=0.0)
    assert out["completed"] == 5 and out["evicted"] == 0
    assert out["new_tokens"] == sum(b for _, b in shapes)
    assert out["doorbells"] > 0
    assert out["tokens_per_doorbell"] == pytest.approx(
        out["new_tokens"] / out["doorbells"])
    for r, t in zip(reqs, tickets):
        assert t.status == "done"
        assert len(t.tokens) == r.max_new_tokens
        ref = Request(r.uid, r.prompt, max_new_tokens=r.max_new_tokens)
        solo.serve([ref])
        assert t.tokens == ref.tokens, f"uid={r.uid} diverged"
        assert r.tokens == t.tokens          # mirrored onto the Request


def test_continuous_decode_launch_shape_stable_across_churn(engine):
    """Join/leave churn must reuse the same compiled multi-token decode:
    jitted launches are keyed by shape, and slot membership never changes
    the stacked state's shape."""
    n_compiles = engine._decode_slots.__wrapped__._cache_size()
    tix = [engine.submit(mk_request(100 + i, 3, 2)) for i in range(3)]
    engine.run(idle_timeout_s=0.0)
    assert all(t.status == "done" for t in tix)
    assert engine._decode_slots.__wrapped__._cache_size() == n_compiles


def test_admission_rejects_when_queue_full(engine):
    """max_pending=8 with policy=reject: overflow submits are refused but
    everything admitted still completes."""
    tix = [engine.submit(mk_request(200 + i, 3, 2)) for i in range(11)]
    rejected = [t for t in tix if t.status == "rejected"]
    assert len(rejected) == 3
    assert all(t.reason == "queue_full" for t in rejected)
    out = engine.run(idle_timeout_s=0.0)
    assert out["completed"] == 8
    assert all(t.status in ("done", "rejected") for t in tix)


def test_admission_rejects_prompt_longer_than_max_seq(engine):
    t = engine.submit(mk_request(300, 33, 2))        # max_seq is 32
    assert t.status == "rejected" and t.reason == "prompt_exceeds_max_seq"
    assert engine.run(idle_timeout_s=0.0)["requests"] == 0


def test_eviction_on_kv_overrun_truncates_to_capacity():
    eng = ContinuousBatchingServer(CFG, batch_size=2, max_seq=8,
                                   tokens_per_launch=2, seed=1,
                                   kv_page_tokens=4)
    ok = eng.submit(mk_request(0, 4, 3))             # fits: cap=5
    greedy = eng.submit(mk_request(1, 6, 10))        # cap = 8-6+1 = 3
    out = eng.run(idle_timeout_s=0.0)
    assert ok.status == "done" and len(ok.tokens) == 3
    assert greedy.status == "evicted" and greedy.reason == "kv_overrun"
    assert len(greedy.tokens) == 3
    assert out["completed"] == 1 and out["evicted"] == 1
    # the served prefix is still the exact greedy chain
    solo = Server(CFG, batch_size=1, max_seq=8, tokens_per_launch=1, seed=1)
    ref = Request(1, greedy.request.prompt, max_new_tokens=3)
    solo.serve([ref])
    assert greedy.tokens == ref.tokens


def test_threaded_replay_requests_join_running_decode():
    """Realtime replay: a producer thread submits Poisson arrivals while
    the decode loop runs; everything lands on one session timeline."""
    eng = ContinuousBatchingServer(CFG, batch_size=2, max_seq=16,
                                   tokens_per_launch=2, seed=1)
    # warm up compiles so arrival pacing isn't swamped by the first launch
    eng.submit(mk_request(999, 4, 2))
    eng.run(idle_timeout_s=0.0)
    spec = TrafficSpec(n_requests=8, rate=400.0, prompt_lens=(4,),
                       new_tokens=(3, 5), seed=3)
    tickets, out = replay(eng, generate(spec, CFG.vocab_size),
                          realtime=True, idle_timeout_s=10.0)
    assert len(tickets) == 8
    assert out["completed"] == 8
    assert out["latency_p99_s"] >= out["latency_p50_s"] >= 0.0
    names = {e.name for e in eng.session.timeline(kinds="progress")}
    assert {"serve.submit", "serve.admit", "serve.finish"} <= names
    # intake closed by the replay harness once the producer drained
    assert eng.queue.closed


# -- scheduler unit tests (no JAX) ------------------------------------------

def test_admission_queue_drop_oldest_policy():
    q = AdmissionQueue(max_pending=2, policy="drop_oldest")
    t = [RequestTicket(request=mk_request(i, 2, 1)) for i in range(3)]
    assert q.submit(t[0]) == (True, None)
    assert q.submit(t[1]) == (True, None)
    accepted, dropped = q.submit(t[2])
    assert accepted and dropped is t[0]
    assert q.pop() is t[1] and q.pop() is t[2] and q.pop() is None
    assert q.n_dropped == 1


def test_admission_queue_close_refuses_and_unknown_policy_raises():
    q = AdmissionQueue(max_pending=2, policy="reject")
    q.close()
    assert q.submit(RequestTicket(request=mk_request(0, 2, 1))) == (False,
                                                                    None)
    assert q.n_refused == 1
    with pytest.raises(ValueError, match="policy"):
        AdmissionQueue(policy="lifo")


def test_percentile_interpolation():
    xs = [0.0, 1.0, 2.0, 3.0]
    assert percentile(xs, 50.0) == pytest.approx(1.5)
    assert percentile(xs, 99.0) == pytest.approx(2.97)
    assert percentile([5.0], 99.0) == 5.0
    assert percentile([], 50.0) == 0.0
    assert percentile([-1.0, 2.0], 50.0) == 2.0      # -1 = "never happened"


# -- traffic generator ------------------------------------------------------

def test_poisson_traffic_deterministic_per_seed():
    spec = TrafficSpec(n_requests=32, rate=100.0, prompt_lens=(4, 8),
                       new_tokens=(2, 6), seed=11)
    a = generate(spec, vocab_size=CFG.vocab_size)
    b = generate(spec, vocab_size=CFG.vocab_size)
    assert [x.t for x in a] == [x.t for x in b]
    assert all(np.array_equal(x.request.prompt, y.request.prompt)
               for x, y in zip(a, b))
    assert [x.request.max_new_tokens for x in a] == \
        [y.request.max_new_tokens for y in b]
    c = generate(TrafficSpec(n_requests=32, rate=100.0, prompt_lens=(4, 8),
                             new_tokens=(2, 6), seed=12), CFG.vocab_size)
    assert [x.t for x in a] != [x.t for x in c]
    # arrivals are ordered and lengths come from the declared choices
    ts = [x.t for x in a]
    assert ts == sorted(ts) and ts[0] > 0.0
    assert {len(x.request.prompt) for x in a} <= {4, 8}
    assert {x.request.max_new_tokens for x in a} <= {2, 6}
