"""The engine step's host phases (``phases.py``), on known steps.

``data/phases_small.json`` holds:

- ``steps``: seven engine steps as :class:`phases.StepSpans` records them:
  five decode steps of 150-152 ms whose host time (the step less its
  read-backs) is 4-7 ms, an idle step of 2 ms, and a step that admits a
  request (a 30 ms first-token read-back) and decodes, 10 ms of host time;
- ``trace``: three benchmark steps of 100 ms on one TPU plane, each with a
  decode launch.  In step 1 the program's spans nest as the engine opens
  them (``serve.step`` > ``serve.decode_iter`` > ``engine.decode`` >
  ``serve.decode.upload``, ``dispatch.decode_slots``,
  ``serve.decode.readback``); the device is idle 100-110 ms, under the
  upload, and 170-201 ms, under the read-back.
"""
import copy
import json
import os

import pytest

import phases
import run
import xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
STEPS = {0: {"active": 1, "decode": [5]}, 1: {"active": 1, "decode": [6]},
         2: {"active": 1, "decode": [7]}}


@pytest.fixture(scope="module")
def data():
    with open(os.path.join(DATA, "phases_small.json")) as f:
        return json.load(f)


def _stalled(steps, ms=1000.0, dispatch=850.0):
    """``steps`` and one more decode step of ``ms``, ``dispatch`` of it in
    the decode launch's dispatch."""
    s = copy.deepcopy(steps)
    s.append({"t0": 5.0, "ms": ms, "decoded": 7, "spans": [
        ["serve.decode.upload", 0.4], ["dispatch.decode_slots", dispatch],
        ["serve.decode.readback", 145.0],
        ["serve.decode_iter", dispatch + 145.4]]})
    return s


def test_host_ms_per_step_takes_the_readbacks_out(data):
    steps = data["steps"]
    assert phases.median_step_ms(steps) == pytest.approx(150.0)
    # host times 4, 5, 6, 5, 7 and 185 - 145 - 30 = 10; the idle step
    # launched no decode and is left out
    assert phases.host_ms_per_step(steps) == pytest.approx(5.5)
    assert phases.host_ms_per_step([steps[5]]) is None


def test_stall_ms_is_zero_without_a_stall(data):
    assert phases.stall_ms(data["steps"]) == 0.0
    assert phases.stalls(data["steps"]) == []


def test_stall_ms_counts_the_time_past_twice_the_median(data):
    steps = _stalled(data["steps"])
    assert phases.median_step_ms(steps) == pytest.approx(150.0)
    assert phases.stall_ms(steps) == pytest.approx(1000.0 - 2 * 150.0)
    (stall,) = phases.stalls(steps)
    assert stall["ms"] == 1000.0
    assert stall["at_s"] == pytest.approx(5.0)
    assert stall["phase"] == "dispatch.decode_slots"
    assert stall["phase_excess_ms"]["dispatch.decode_slots"] == \
        pytest.approx(850.0 - 1.1)
    assert stall["phase_share"] >= 0.9
    assert sum(stall["phases_ms"].values()) == pytest.approx(1000.0)


def test_stall_in_the_readback_is_named_so(data):
    steps = _stalled(data["steps"], ms=1000.0, dispatch=1.1)
    steps[-1]["spans"][2][1] = 1000.0 - 0.4 - 1.1 - 4.0
    (stall,) = phases.stalls(steps)
    assert stall["phase"] == "serve.decode.readback"


def test_idle_gap_is_named_by_the_innermost_program_span(data):
    reduced = xtrace.reduce(data["trace"], STEPS)
    assert reduced["idle_gaps"] == [
        ["serve.decode.readback", pytest.approx(0.031)],
        ["serve.decode.upload", pytest.approx(0.010)],
        ["bench.step", pytest.approx(0.001)]]
    # without the program's spans only the labels change
    bench = dict(data["trace"], host=[h for h in data["trace"]["host"]
                                      if h[0].startswith(("bench.",
                                                          "engine."))])
    coarse = xtrace.reduce(bench, STEPS)
    assert [g[0] for g in coarse["idle_gaps"]] == [
        "engine.decode", "engine.decode", "bench.step"]
    for k in reduced:
        if k != "idle_gaps":
            assert reduced[k] == coarse[k], k


def test_run_on_the_cpu_records_the_window_steps():
    """A whole run of a tiny cell: the window's steps are found, and each
    decode step holds its upload, launch and read-back."""
    def load(name):
        with open(os.path.join(DATA, name)) as f:
            return json.load(f)
    cfg = load("tiny.json")
    cell = {"name": "tiny", "chips": 1, "config": cfg,
            "arch": run.load_arch(cfg), "mix": load("tiny-mix.json"),
            "limits": load("tiny-limits.json"), "end_to_end": [],
            "per_layer": []}
    log = phases.StepSpans()
    res = run.execute(cell, 1001, 1.0, False, require_tpu=False,
                      fault=log.attach)
    assert res["correct"]
    assert len(log.marks) == 2
    window = log.window()
    decode = [s for s in window if s["decoded"]]
    assert decode and len(window) < len(log.steps)
    names = {n for n, _ in decode[0]["spans"]}
    assert {"serve.decode.upload", "dispatch.decode_slots",
            "serve.decode.readback", "serve.decode_iter"} <= names
    summary = phases.summarize(window)
    assert summary["host_ms_per_step"] > 0
    assert summary["stall_ms"] >= 0
    json.dumps(summary)
