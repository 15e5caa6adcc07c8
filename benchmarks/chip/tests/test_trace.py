"""The trace reduction on a small recorded trace with known answers.

``data/trace_small.json`` is four benchmark steps of 100 ms on one TPU
plane, in the event-list form ``xtrace.load`` returns:

- step 0: a decode launch; it ends in a host sync, so the slice starts
  after it;
- step 1 (1 slot active): a decode launch 110-160 ms under engine.decode,
  whose ``while`` (130-160 ms) holds a fusion of its body (135-150 ms) on
  the same line, as the TPU trace nests them;
- step 2 (nothing active): the loop waits for an arrival, 220-290 ms;
- step 3 (2 active): an extend launch 310-340 ms, a decode 350-390 ms.
"""
import json
import os
from types import SimpleNamespace

import pytest

import readers
import run
import xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
STEPS = {0: {"active": 1, "decode": [5]}, 1: {"active": 1, "decode": [6]},
         2: {"active": 0, "decode": None},
         3: {"active": 2, "decode": [7, 8]}}


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(DATA, "trace_small.json")) as f:
        return xtrace.reduce(json.load(f), STEPS)


def test_busy_and_window(reduced):
    assert reduced["window_s"] == pytest.approx(0.300)
    assert reduced["busy_s"] == pytest.approx(0.050 + 0.030 + 0.040)


def test_idle_counts_only_steps_with_an_active_slot(reduced):
    # steps 1 and 3 span 200 ms, of which 120 ms are busy
    assert reduced["active_s"] == pytest.approx(0.200)
    assert reduced["idle_active_s"] == pytest.approx(0.080)


def test_program_time_per_step(reduced):
    assert reduced["programs_s"] == pytest.approx(
        {"jit_decode_paged": 0.090, "jit_extend_paged": 0.030})
    assert reduced["steps"] == [1, 2, 3]
    assert reduced["per_step"][1]["jit_decode_paged"] == pytest.approx(
        (1, 0.050))
    assert reduced["per_step"][3]["jit_extend_paged"] == pytest.approx(
        (1, 0.030))
    assert 2 not in reduced["per_step"] and 0 not in reduced["per_step"]


def test_breakdown_names_are_stable(reduced):
    ops = dict(reduced["device_ops"])
    assert ops == pytest.approx({
        "jit_decode_paged:fusion": 0.055,
        "jit_decode_paged:while": 0.015,
        "jit_extend_paged:fusion": 0.030,
        "jit_decode_paged:copy": 0.020})


def test_idle_gaps_named_by_the_host(reduced):
    gaps = reduced["idle_gaps"]
    assert gaps[0] == ["bench.wait", pytest.approx(0.150)]
    assert sorted(g[0] for g in gaps[1:]) == ["bench.step", "engine.decode",
                                              "engine.prefill"]


def test_rooflines_count_the_slice_through_the_plugin(reduced):
    """The slice's work, counted by the plug-in the configuration names, at
    DeepSeek-7B-l10's widths, T = 4: decode launches from [600] (step 1) and
    [700, 1800] (step 3), and one extend chunk of 512 from 1024 (step 3).

    Decode bytes (``test_counts.py``'s terms): weights 4,886,364,160 a
    step; step 1 adds (601+...+604)*163,840 of KV and 4 rows of 8,192,
    19,940,343,808 in all; step 3 (701..704, 1801..1804) 21,187,198,976.
    Extend FLOPs: linear 2,072,321,720,320, attention 4*10*32*128*655,616
    = 107,416,125,440, logits 838,860,800.  Device time: decode 90 ms,
    extend 30 ms (``test_program_time_per_step``)."""
    with open(os.path.join(os.path.dirname(DATA), os.pardir, "configs",
                           "deepseek-7b-l10.json")) as f:
        cfg = json.load(f)
    steps = {k: dict(v, prefills=[], note=None) for k, v in STEPS.items()}
    steps[1]["decode"], steps[3]["decode"] = [600], [700, 1800]
    steps[3]["prefills"] = [(1024, 512)]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    rec = SimpleNamespace(arch=run.load_arch(cfg), model=cfg["model"], T=4,
                          steps=steps, trace=reduced, peaks=peaks)
    nbytes = 19_940_343_808 + 21_187_198_976
    flops = 2_072_321_720_320 + 107_416_125_440 + 838_860_800
    assert readers.decode_work(rec, reduced["steps"])[1] == nbytes
    assert readers.prefill_work(rec) == (512, flops)
    assert readers.decode_hbm_roofline(rec) == pytest.approx(
        100 * nbytes / 819e9 / 0.090, rel=1e-12)
    assert readers.prefill_flop_roofline(rec) == pytest.approx(
        100 * flops / 197e12 / 0.030, rel=1e-12)


def test_nothing_to_read():
    assert xtrace.reduce({"modules": [], "ops": [], "host": []}, STEPS) is None
    with open(os.path.join(DATA, "trace_small.json")) as f:
        ev = json.load(f)
    one_sync = {**STEPS, 1: {"active": 1, "decode": None},
                3: {"active": 2, "decode": None}}
    assert xtrace.reduce(ev, one_sync) is None


@pytest.mark.parametrize("raw,want", [
    ("jit_decode_paged(8769873378064142202)", "jit_decode_paged"),
    ("jit_decode_paged_8769873378064142202_", "jit_decode_paged"),
    ("fusion.171", "fusion"),
    ("%convolution_tanh_fusion.2 = bf16[8,1024]{1,0} fusion(...)",
     "convolution_tanh_fusion"),
    ("while.123", "while"),
    ("copy", "copy"),
])
def test_stable_names(raw, want):
    assert xtrace.stable(raw) == want
