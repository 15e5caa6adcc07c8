"""Every seed gets the same work: counts, lengths and arrivals per block."""
import collections
import json
import os

import numpy as np
import pytest

import schedule

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")
MIXES = sorted(f[:-5] for f in os.listdir(TRAFFIC) if f.endswith(".json"))
SEEDS = list(range(12)) + [2 ** 31 + 12345, 3 * 2 ** 32 + 7]


def mix(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


def signature(sched, dur):
    lengths = collections.Counter((len(it.prompt), it.max_new)
                                  for it in sched.window_items)
    per_block = collections.Counter(it.block for it in sched.items)
    lead = sched.window[0]
    in_block = collections.Counter(
        int((it.due - lead) / dur + 1e-9) if dur and it.block >= 0
        else it.block
        for it in sched.items)
    lead_in = [(it.due, len(it.prompt), it.max_new) for it in sched.items
               if it.block < 0]
    return (len(sched.window_items), len(sched.items), lengths,
            per_block, in_block, sched.window, lead_in)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    m = mix(name)
    dur = m["block"] / m["rate_rps"] if m["kind"] == "open" else 0.0
    sigs = [signature(schedule.build(m, 1000, s, 30.0), dur) for s in SEEDS]
    assert all(s == sigs[0] for s in sigs[1:])
    n_win, n_all, lengths, per_block, in_block, _, _ = sigs[0]
    assert {n for b, n in per_block.items() if b >= 0} == {m["block"]}
    # arrivals stay inside their own block, whatever the permutation
    assert in_block == per_block


@pytest.mark.parametrize("name", MIXES)
def test_seeds_change_only_the_tokens(name):
    m = mix(name)
    a = schedule.build(m, 1000, 0, 30.0)
    b = schedule.build(m, 1000, 1, 30.0)
    c = schedule.build(m, 1000, 0, 30.0)
    assert [(i.due, len(i.prompt), i.max_new) for i in a.items] == [
        (i.due, len(i.prompt), i.max_new) for i in b.items]
    assert not all(np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a.items, b.items))
    assert all(np.array_equal(x.prompt, y.prompt) and x.due == y.due
               for x, y in zip(a.items, c.items))


def test_blocks_differ_in_order():
    sched = schedule.build(mix("chat-prefix"), 1000, 0, 51.0)
    orders = {}
    for it in sched.items:
        orders.setdefault(it.block, []).append((len(it.prompt), it.max_new))
    assert orders[0] != orders[1]


@pytest.mark.parametrize("name", [n for n in MIXES
                                  if mix(n)["kind"] == "open"])
def test_open_window_fills_the_run(name):
    """At the run length of BENCHMARK.json every latency window is a whole
    number of blocks within two seconds of it, holding some tens of
    requests at least."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(TRAFFIC)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    sched = schedule.build(mix(name), 1000, 0, seconds)
    lo, hi = sched.window
    assert abs((hi - lo) - seconds) <= 2.0
    assert len(sched.window_items) >= 20
    assert all(lo <= it.due < hi for it in sched.window_items)


def test_block_shares_are_exact():
    m = mix("chat-prefix")
    tuples = schedule.block_tuples(m)
    assert collections.Counter(t[1] for t in tuples) == {64: 8, 128: 6,
                                                         256: 4, 512: 2}
    assert collections.Counter(t[2] for t in tuples) == {32: 6, 64: 6,
                                                         128: 5, 256: 3}
    assert collections.Counter(t[0] for t in tuples) == {0: 8, 1: 6, 2: 4,
                                                         3: 2}


def test_share_that_does_not_divide_the_block_is_refused():
    m = dict(mix("chat-prefix"), block=7)
    with pytest.raises(ValueError):
        schedule.block_tuples(m)


def test_warm_up_reaches_every_prompt_length():
    m = mix("chat-prefix")
    sched = schedule.build(m, 1000, 5, 10.0)
    warm = schedule.warm_items(m, sched, 1000, 4)
    assert {len(w.prompt) for w in warm} == {1024 + n for n in
                                             m["prompt"]["lengths"]}
    assert {tuple(w.prompt[:1024]) for w in warm} == {
        tuple(p) for p in sched.prefixes}
