"""Latencies are timed on the loop's own clock from the due time."""
import types

import numpy as np

import readers
import run


class FakeKV:
    def __init__(self):
        self.lengths = np.zeros(2, np.int32)
        self.ready = np.zeros(2, bool)

    def begin(self, *a):
        return True

    def prefill_step(self, *a):
        return 0

    def reserve_decode(self, *a):
        return []

    def decode(self):
        return np.zeros((2, 1), np.int32)

    def release(self, *a):
        pass


class FakeEngine:
    """Admits a request one step after it is submitted, gives its first
    token the step after that and one more token per step."""

    def __init__(self, clock):
        self.kv = FakeKV()
        self.clock = clock
        self.n_active = 0
        self.tickets = []

    def submit(self, request):
        t = types.SimpleNamespace(request=request, tokens=[], t_admit=-1.0,
                                  t_submit=self.clock(), status="queued")
        t.finished = False
        self.tickets.append(t)
        return t

    def step(self):
        self.clock.advance(0.25)
        for t in self.tickets:
            if t.finished:
                continue
            if t.t_admit < 0:
                t.t_admit = self.clock()
                t.status = "active"
            else:
                t.tokens.append(1)
                if len(t.tokens) == t.request.max_new_tokens:
                    t.status, t.finished = "done", True
        return True


class Clock:
    def __init__(self):
        self.t = 10.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def test_ttft_counts_from_due_time_not_from_submit():
    clock = Clock()
    eng = FakeEngine(clock)
    loop = run.Loop(eng, run.SpanLog(), clock=clock)
    # due at 9.0, but the loop only gets round to submitting it at 10.0
    r = run.Req(uid=0, due=9.0, prompt=np.zeros(4, np.int32), max_new=3)
    loop.submit(r)
    while not r.ticket.finished:
        loop.step()
    # admitted at 10.25, first token at 10.5, last at 11.0
    assert r.admit == 10.25
    assert r.first == 10.5 and r.last == 11.0 and r.n_tokens == 3
    assert readers.ttft_s(r, giveup=99.0) == 1.5          # not 0.5
    assert readers.queue_wait_s(r, giveup=99.0) == 1.25
    assert readers.tpot_s(r, giveup=99.0) == 0.25


def test_a_pause_stops_the_loop_clock():
    """The profiler stalls the loop while it stops; the loop's clock stands
    still meanwhile, and the engine's own stamps are read against it."""
    clock = Clock()
    eng = FakeEngine(clock)
    loop = run.Loop(eng, run.SpanLog(), clock=clock)
    r = run.Req(uid=0, due=10.0, prompt=np.zeros(4, np.int32), max_new=3)
    loop.submit(r)
    loop.step()                                   # admitted at 10.25
    loop.pause(lambda: clock.advance(3.0))
    assert loop.clock() == 10.25
    late = run.Req(uid=1, due=10.25, prompt=np.zeros(4, np.int32), max_new=2)
    loop.submit(late)
    while not (r.ticket.finished and late.ticket.finished):
        loop.step()
    assert r.admit == 10.25 and r.first == 10.5 and r.last == 11.0
    assert late.admit == 10.5 and late.first == 10.75
    assert readers.ttft_s(r, giveup=99.0) == 0.5


def test_failed_request_is_later_than_every_other():
    done = run.Req(uid=0, due=0.0, prompt=np.zeros(1), max_new=2,
                   first=5.0, last=6.0, admit=1.0, n_tokens=2)
    lost = run.Req(uid=1, due=1.0, prompt=np.zeros(1), max_new=2,
                   failed=True)
    giveup = 70.0
    vals = [readers.ttft_s(r, giveup) for r in (done, lost)]
    assert vals[1] > vals[0]
    assert readers.tpot_s(lost, giveup) > readers.tpot_s(done, giveup)
    assert readers.p90(vals) == vals[1]


def test_p90_is_nearest_rank():
    assert readers.p90(range(1, 101)) == 90
    assert readers.p90(range(1, 11)) == 9
    assert readers.p90([]) is None
