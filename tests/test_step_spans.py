"""The engine step's host phases as program spans.

One scheduler step is the ``serve.step`` span.  Admission, the decode
launch's table upload and its token read-back, and the prefill's first-token
read-back are spans inside it; the launch itself stays the doorbell's
``dispatch`` event, stamped with ``serve.decode_iter``.  Scoped spans and
doorbell dispatches are also profiler annotations, so a ``jax.profiler``
trace shows them on its host plane.
"""
import glob

import jax
import numpy as np
import pytest

from repro.configs import SMOKE_ARCHS
from repro.core.session import SPAN_EVENT, TraceSession
from repro.runtime.server import ContinuousBatchingServer, Request

CFG = SMOKE_ARCHS["gemma-2b"]


class ListSink:
    def __init__(self):
        self.events = []

    def emit(self, event):
        self.events.append(event)


def _engine():
    sink = ListSink()
    sess = TraceSession(name="test_step_spans", sinks=[sink])
    eng = ContinuousBatchingServer(CFG, batch_size=2, max_seq=64,
                                   tokens_per_launch=4, seed=0, session=sess,
                                   kv_page_tokens=16)
    return eng, sink


def _request(uid, n=8):
    prompt = np.random.default_rng(uid).integers(
        0, CFG.vocab_size, n).astype(np.int32)
    return Request(uid=uid, prompt=prompt, max_new_tokens=12)


def _span_paths(events):
    return [e.meta["span_path"] for e in events if e.name == SPAN_EVENT
            and e.meta["span"] != "serve.request"]


@pytest.mark.parametrize("kv,prefill,upload", [
    ("paged", "prefill_extend", ["serve.step/serve.decode_iter/"
                                 "serve.decode.upload"]),
])
def test_one_step_yields_the_span_tree(kv, prefill, upload):
    eng, sink = _engine()
    eng.submit(_request(1))
    assert eng.step()
    assert _span_paths(sink.events) == [
        "serve.step/serve.admit/serve.prefill",
        "serve.step/serve.admit/serve.prefill.readback",
        "serve.step/serve.admit",
    ] + upload + [
        "serve.step/serve.decode_iter/serve.decode.readback",
        "serve.step/serve.decode_iter",
        "serve.step",
    ]
    step = [e for e in sink.events if e.name == SPAN_EVENT
            and e.meta["span"] == "serve.step"][0]
    assert (step.meta["active"], step.meta["admitted"],
            step.meta["decoded"]) == (1, 1, 1)
    children = [e for e in sink.events if e.name == SPAN_EVENT
                and e.meta.get("parent_span_id") == step.meta["span_id"]]
    assert sum(e.dur_s for e in children) <= step.dur_s
    # the launches stay the doorbell's dispatch events, outside the phases
    dispatch = {e.name: e.meta["span_path"] for e in sink.events
                if e.kind == "dispatch"}
    assert dispatch == {
        prefill: "serve.step/serve.admit/serve.prefill",
        "decode_slots": "serve.step/serve.decode_iter"}


def test_idle_step_emits_no_admit():
    eng, sink = _engine()
    assert not eng.step()
    assert _span_paths(sink.events) == ["serve.step"]
    step = sink.events[-1]
    assert (step.meta["active"], step.meta["admitted"],
            step.meta["decoded"]) == (0, 0, 0)


def test_full_engine_opens_no_admit_for_a_queued_request():
    eng, sink = _engine()
    for uid in range(3):                # two slots, one request waits
        eng.submit(_request(uid))
    eng.step()
    n0 = len(sink.events)
    eng.step()
    assert len(eng.queue) == 1
    assert "serve.step/serve.admit" not in _span_paths(sink.events[n0:])


def _host_event_names(path):
    from jax.profiler import ProfileData
    files = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
    assert files, "the profiler wrote no trace"
    pd = ProfileData.from_file(files[0])
    return {ev.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


def test_profiler_trace_holds_the_program_spans(tmp_path):
    eng, _ = _engine()
    eng.submit(_request(1))
    eng.step()                          # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
        eng.step()
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(str(tmp_path))
    assert {"serve.step", "serve.decode_iter", "serve.decode.upload",
            "serve.decode.readback", "dispatch.decode_slots"} <= names
    # a request's span crosses steps: it stays off the profiler's trace
    assert "serve.request" not in names
