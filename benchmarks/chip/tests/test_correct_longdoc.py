"""What decides ``correct``, at the long-document cell's ratios.

``test_correct.py``'s runs of the whole harness, on a tiny decoder shaped
like Qwen3-8B (grouped-query attention 4:1, qk-norm, rope theta 1e6,
untied) under a mix of unshared prompts of 4 to 8 chunks each, with
outputs of several decode launches: a sound run is correct, the fp8
control is not, and each planted fault is not.  Beside them, the reader of
``extend_live_attention_share``.
"""
import json
import os
from types import SimpleNamespace

import pytest

import run
from test_correct import (  # noqa: F401  (collected here with this cell)
    test_fault_in_the_timed_path_is_not_correct,
    test_sound_run_is_correct_and_control_is_not)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cell():
    cfg = _load("tiny-qwen3.json")
    return {"name": "tiny-longdoc", "chips": 1, "config": cfg,
            "arch": run.load_arch(cfg), "mix": _load("tiny-longdoc.json"),
            "limits": _load("tiny-limits.json"),
            "end_to_end": [], "per_layer": []}


@pytest.mark.parametrize("delta", [
    {"prefix_hit_tokens": 0},
    {"extend_scored_positions": 0, "extend_live_positions": 0}],
    ids=["no-counters", "no-extend"])
def test_extend_share_is_none_without_counts(delta):
    read = run.load_reader("extend_live_attention_share")
    assert read(SimpleNamespace(kv_delta=delta)) is None


def test_extend_share_matches_the_window_prefills(cell, monkeypatch):
    """The counters' window delta gives the share that the window's
    prefill spans give, each chunk of C positions from ``start`` scoring
    C·S pairs and keeping C·start + C(C+1)/2."""
    records = []
    load = run.load_reader

    def keep_record(name):
        read = load(name)

        def reader(rec):
            records.append(rec)
            return read(rec)
        return reader

    monkeypatch.setattr(run, "load_reader", keep_record)
    # an untraced run reads the end-to-end list: the reader goes there
    metrics = [{"name": "extend_live_attention_share", "unit": "%"}]
    res = run.execute(dict(cell, end_to_end=metrics), 1001, 1.0, False,
                      require_tpu=False)
    assert res["correct"], res["checks"]
    rec = records[0]
    S = cell["config"]["engine"]["max_seq"]
    chunks = [p for k in rec.window_steps for p in rec.steps[k]["prefills"]]
    assert chunks
    live = sum(c * a + c * (c + 1) // 2 for a, c in chunks)
    scored = sum(c * S for _, c in chunks)
    got = res["metrics"]
    assert got["extend_live_attention_share"]["value"] == pytest.approx(
        100.0 * live / scored)
