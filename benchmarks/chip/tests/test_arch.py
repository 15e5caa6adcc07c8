"""Architecture plug-ins: a configuration's ``arch`` names the file
``arch/<arch>.py`` that holds everything the harness does differently for
that architecture, and the harness finds it by that name alone."""
import json
import os
import shutil

import pytest

import run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CONTRACT = ("model_config", "shapes", "make", "logits", "prefill_flops",
            "decode_flops_bytes")

# A plug-in that no file of the harness names: the dense one, with every
# call counted, and a note on each decode launch (the number of slots it
# decodes) that has to reach that launch's count.
COUNTING = '''
import importlib.util
import os

import run

_spec = importlib.util.spec_from_file_location(
    "counted_dense", os.path.join(run.HERE, "arch", "dense.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)
CALLS = {}
NOTED = []


def _counted(name):
    fn = getattr(base, name)

    def call(*a, **k):
        CALLS[name] = CALLS.get(name, 0) + 1
        return fn(*a, **k)
    return call


for _name in %r:
    globals()[_name] = _counted(_name)
_decode_flops_bytes = decode_flops_bytes


def decode_flops_bytes(model, lengths, T, note):
    NOTED.append((len(lengths), note))
    return _decode_flops_bytes(model, lengths, T, note)


def note(eng):
    return int(eng.kv.ready.sum())
''' % (CONTRACT,)


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


@pytest.mark.parametrize("path", sorted(
    [os.path.join(os.path.dirname(DATA), os.pardir, "configs", n)
     for n in os.listdir(os.path.join(run.HERE, "configs"))]
    + [os.path.join(DATA, n) for n in ("tiny.json", "tiny-qwen3.json")]),
    ids=os.path.basename)
def test_every_configuration_names_a_plugin(path):
    with open(path) as f:
        cfg = json.load(f)
    arch = run.load_arch(cfg)
    for name in CONTRACT:
        assert callable(getattr(arch, name)), name


@pytest.mark.parametrize("arch", [None, "moe-latent", "../arch/dense"],
                         ids=["missing", "unknown", "not-a-name"])
def test_unknown_arch_stops_lookup(tmp_path, monkeypatch, arch):
    """``lookup`` of a real cell whose configuration names no plug-in, or
    one with no file, stops with the path it looked for."""
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    conf = next(c for c in bench["configs"] if c["name"] == "deepseek-7b-l10")
    with open(os.path.join(run.ROOT, conf["file"])) as f:
        cfg = json.load(f)
    cfg.pop("arch")
    if arch is not None:
        cfg["arch"] = arch
    dest = tmp_path / conf["file"]
    dest.parent.mkdir(parents=True)
    dest.write_text(json.dumps(cfg))
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    with pytest.raises(SystemExit) as exc:
        run.lookup("deepseek-7b-l10.chat-prefix")
    msg = str(exc.value)
    assert "deepseek-7b-l10" in msg
    want = "<arch>" if arch is None else arch
    assert os.path.join(run.ARCH_DIR, want + ".py") in msg


def test_a_new_plugin_file_serves_a_whole_run(tmp_path, monkeypatch):
    """A plug-in added as a file and named by a configuration's ``arch``
    builds the engine, makes the weights, counts the work and is the
    reference of a whole run, which comes out correct."""
    (tmp_path / "counting.py").write_text(COUNTING)
    monkeypatch.setattr(run, "ARCH_DIR", str(tmp_path))
    cfg = dict(_load("tiny.json"), arch="counting")
    arch = run.load_arch(cfg)
    assert arch.CALLS == {}
    # an untraced run reads the end-to-end list; the window's FLOPs need
    # both counts (the value itself is dropped: the CPU has no peaks)
    cell = {"name": "tiny", "chips": 1, "config": cfg, "arch": arch,
            "mix": _load("tiny-mix.json"),
            "limits": _load("tiny-limits.json"),
            "end_to_end": [{"name": "mfu.offline", "unit": "%"}],
            "per_layer": []}
    res = run.execute(cell, 1001, 1.0, False, require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["info"]["checked_tokens"] > 0
    calls = arch.CALLS
    assert calls["model_config"] == calls["shapes"] == calls["make"] == 1
    assert calls["logits"] == res["info"]["checked_requests"] > 0
    assert calls["prefill_flops"] > 0 and calls["decode_flops_bytes"] > 0
    # each decode launch's count got the note taken before that launch
    assert arch.NOTED and all(n == slots for slots, n in arch.NOTED)
