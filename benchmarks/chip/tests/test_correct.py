"""What decides ``correct``, driven end to end at a size the CPU holds.

A whole run of the harness (engine, loop, check) on a tiny dense decoder,
without the harness's look for a chip.  A sound run is correct; the fp8
control, put in the program's place, is not; and each fault a serving cell
can have, planted in the timed path, turns ``correct`` false.
"""
import json
import os

import numpy as np
import pytest

import faults
import run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cell():
    cfg = _load("tiny.json")
    return {"name": "tiny", "chips": 1, "config": cfg,
            "arch": run.load_arch(cfg), "mix": _load("tiny-mix.json"),
            "limits": _load("tiny-limits.json"), "end_to_end": [],
            "per_layer": []}


def _run(cell, seed, **kw):
    return run.execute(cell, seed, 1.0, False, require_tpu=False, **kw)


@pytest.mark.parametrize("seed", [1001, 1002, 1005])
def test_sound_run_is_correct_and_control_is_not(cell, seed):
    limit = cell["limits"]["max_logit_gap"]
    res = _run(cell, seed)
    assert res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] <= limit
    assert list(res)[-1] == "checks"
    # the fp8 control in the program's place: the same run, the same limit
    ctl = _run(cell, seed, control=True)
    assert not ctl["correct"], ctl["checks"]
    assert ctl["checks"]["max_logit_gap"]["value"] > limit
    assert ctl["info"]["program_max_logit_gap"] <= limit
    assert list(ctl)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(faults.ALL))
def test_fault_in_the_timed_path_is_not_correct(cell, fault):
    res = _run(cell, 1001, fault=faults.ALL[fault])
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > cell["limits"][
        "max_logit_gap"]


def test_sample_holds_the_longest(cell):
    import check
    reqs = [run.Served(uid=i, prompt=np.zeros(n, np.int32), tokens=[1] * m,
                       max_new=m) for i, (n, m) in enumerate(
                           [(10, 4), (50, 8), (20, 2), (5, 30)])]
    got = check.sample(reqs, np.random.default_rng(0), 1, 1)
    assert [r.uid for r in got] == [1]
    got = check.sample(reqs, np.random.default_rng(0), 100, 8)
    assert sorted(r.uid for r in got) == [0, 1, 2, 3]
