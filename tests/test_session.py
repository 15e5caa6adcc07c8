"""Unified TraceSession: ordering, sinks, ambient activation, legacy parity."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CommandStreamCapture, DoorbellTracker, ExecGraph,
                        HybridMover, JsonlSink, ProgressTracker, RingBufferSink,
                        TraceEvent, TraceSession, current_session)


# -- event ordering across mixed kinds -------------------------------------

def test_mixed_kinds_share_one_monotonic_sequence():
    with TraceSession("mix") as sess:
        cs = sess.capture.lower_and_compile("f", lambda x: x * 2,
                                            args=(jnp.ones(4),))
        f = sess.wrap(cs.compiled, "f_dispatch")
        f(jnp.ones(4))
        sess.mover.put(np.zeros(8, np.float32))
        f(jnp.ones(4))
        tok = sess.progress.release(jnp.ones(2))
        sess.progress.wait(tok)
    evs = sess.timeline()
    assert [e.seq for e in evs] == list(range(len(evs)))
    assert [e.kind for e in evs] == ["compile", "dispatch", "transfer",
                                    "dispatch", "progress", "progress"]
    # one shared timestamp base: t is non-negative and bounded by the wall
    assert all(e.t >= 0 for e in evs)


def test_timeline_filters_and_graph_launch_interleaving():
    with TraceSession("graphs") as sess:
        g = ExecGraph(chain_len=3, width=16)
        g.launch("per_op", session=sess)
        g.launch("multistep", session=sess)
    launches = sess.timeline(kinds="graph_launch")
    assert [e.meta["mode"] for e in launches] == ["per_op", "multistep"]
    assert launches[0].meta["doorbells"] == 3
    assert launches[1].meta["doorbells"] == 1
    # the per-op doorbell rings appear on the same timeline, before the
    # multistep launch event
    dispatches = sess.timeline(kinds="dispatch", name="per_op_dispatch")
    assert len(dispatches) == 3
    assert all(d.seq < launches[1].seq for d in dispatches)


# -- ring buffer bounding ---------------------------------------------------

def test_ring_buffer_bounded_keeps_latest():
    sess = TraceSession("ring", ring_size=10)
    for i in range(25):
        sess.emit("dispatch", f"d{i}")
    assert sess.n_events == 25
    evs = sess.timeline()
    assert len(evs) == 10
    assert [e.name for e in evs] == [f"d{i}" for i in range(15, 25)]
    assert sess.ring.dropped == 15
    assert sess.summary()["dropped"] == 15


def test_emit_rejects_unknown_kind():
    sess = TraceSession("bad")
    with pytest.raises(ValueError):
        sess.emit("doorbell", "nope")


# -- JSONL sink round-trip --------------------------------------------------

def test_jsonl_sink_round_trip(tmp_path):
    path = os.path.join(tmp_path, "trace.jsonl")
    with TraceSession("jsonl", jsonl_path=path) as sess:
        sess.emit("dispatch", "a", dur_s=1e-3, payload_bytes=64, mode="x")
        sess.emit("transfer", "b", complete_s=2e-3)
    loaded = JsonlSink.load(path)
    assert [e.to_dict() for e in loaded] == \
        [e.to_dict() for e in sess.timeline()]
    # file is valid JSONL
    with open(path) as f:
        lines = [json.loads(l) for l in f]
    assert len(lines) == 2 and lines[0]["meta"] == {"mode": "x"}


def test_custom_sink_receives_every_event():
    sink = RingBufferSink(maxlen=100)
    with TraceSession("sinks", sinks=[sink]) as sess:
        sess.emit("progress", "p")
        sess.emit("dispatch", "d")
    assert [e.name for e in sink.events()] == ["p", "d"]


# -- ambient activation (contextvars) ---------------------------------------

def test_ambient_session_install_and_teardown():
    assert current_session() is None
    with TraceSession("outer") as outer:
        assert current_session() is outer
        with TraceSession("inner") as inner:
            assert current_session() is inner
        assert current_session() is outer
    assert current_session() is None


def test_tracker_created_before_session_reports_into_it():
    tracker = DoorbellTracker()          # armed before any session exists
    with TraceSession("late") as sess:
        tracker.ring("late_ring", payload=7)
    assert tracker.count == 1
    evs = sess.timeline(kinds="dispatch")
    assert len(evs) == 1 and evs[0].name == "late_ring"
    assert evs[0].payload_bytes == 7
    # outside the block, the same tracker is silent again
    tracker.ring("after")
    assert sess.n_events == 1


def test_explicit_injection_wins_over_ambient():
    mine = TraceSession("mine")
    tracker = DoorbellTracker(session=mine)
    with TraceSession("ambient") as amb:
        tracker.ring("ding")
    assert len(mine.timeline()) == 1
    assert len(amb.timeline()) == 0


# -- legacy standalone entry points record identically -----------------------

def test_doorbell_standalone_records_identically():
    def run_one():
        t = DoorbellTracker()
        wrapped = t.wrap(lambda x: x + 1, "inc", block=True)
        wrapped(jnp.ones(4))
        t.ring("manual", payload=3)
        return t

    bare = run_one()
    with TraceSession("wrapped"):
        inside = run_one()
    for a, b in zip(bare.records, inside.records):
        assert (a.seq, a.name, a.payload_bytes) == \
            (b.seq, b.name, b.payload_bytes)
    assert bare.summary()["by_name"].keys() == \
        inside.summary()["by_name"].keys()
    assert bare.count == inside.count == 2


def test_doorbell_counts_stay_exact_past_the_record_cap():
    from repro.core.doorbell import MAX_RECORDS
    t = DoorbellTracker()
    for _ in range(MAX_RECORDS + 10):
        t.ring("a")
    for _ in range(5):
        t.ring("b", payload=3)
    assert len(t.records) == MAX_RECORDS
    assert t.records[-1].seq == MAX_RECORDS + 14
    assert t.count == MAX_RECORDS + 15
    assert (t.count_for("a"), t.count_for("b")) == (MAX_RECORDS + 10, 5)
    assert (t.total_payload(), t.total_payload("b")) == (15, 15)
    assert t.summary()["by_name"]["a"]["doorbells"] == MAX_RECORDS + 10
    t.reset()
    assert t.count == t.count_for("a") == 0 and not t.records


def test_capture_standalone_records_identically():
    def run_one():
        cap = CommandStreamCapture()
        return cap.lower_and_compile("g", lambda x: x @ x,
                                     args=(jnp.ones((4, 4)),))

    bare = run_one()
    with TraceSession("wrapped") as sess:
        inside = run_one()
    assert bare.name == inside.name == "g"
    assert bare.n_ops == inside.n_ops
    assert bare.command_bytes == inside.command_bytes
    assert [e.kind for e in sess.timeline()] == ["compile"]


def test_wrap_preserves_function_metadata():
    def my_dispatch(x):
        """docstring survives wrapping"""
        return x

    t = DoorbellTracker()
    wrapped = t.wrap(my_dispatch, "d")
    assert wrapped.__name__ == "my_dispatch"
    assert wrapped.__doc__ == "docstring survives wrapping"


def test_hybrid_mover_and_progress_legacy_paths():
    mover = HybridMover(threshold=1024)
    _, rec = mover.put(np.zeros(16, np.float32))
    assert rec.mode == "inline" and mover.stats()["inline"] == 1
    pt = ProgressTracker()
    tok = pt.release(jnp.ones(2))
    pt.wait(tok)
    assert tok.completed


# -- one session drives trainer AND server (acceptance criterion) -----------

def test_one_session_drives_trainer_and_server():
    from repro.configs import SMOKE_ARCHS
    from repro.configs.shapes import ShapeConfig
    from repro.runtime.server import Request, Server
    from repro.runtime.trainer import Trainer

    cfg = SMOKE_ARCHS["deepseek-7b"]
    shape = ShapeConfig("tiny", 64, 4, "train")
    sess = TraceSession("prod")
    tr = Trainer(cfg, shape, steps_per_launch=2, session=sess)
    out = tr.train(2)
    srv = Server(cfg, batch_size=2, max_seq=64, session=sess)
    o = srv.serve([Request(0, np.arange(4, dtype=np.int32),
                           max_new_tokens=4)])
    assert tr.session is srv.session is sess
    assert out["doorbells"] == 1 and o["doorbells"] >= 2
    evs = sess.timeline()
    assert [e.seq for e in evs] == list(range(len(evs)))
    names = {e.name for e in evs}
    assert "train_k_steps" in names          # trainer dispatch
    assert "prefill" in names                # server dispatch
    kinds = {e.kind for e in evs}
    assert {"dispatch", "progress"} <= kinds


# -- summary / report -------------------------------------------------------

def test_summary_is_json_serializable_and_counts_by_kind():
    with TraceSession("summ") as sess:
        sess.mover.put(np.zeros(4, np.float32))
        sess.emit("dispatch", "d", payload_bytes=10)
        sess.emit("dispatch", "d", payload_bytes=5)
    s = sess.summary()
    json.dumps(s)               # must not raise
    assert s["by_kind"] == {"transfer": 1, "dispatch": 2}
    assert s["by_name"]["d"]["events"] == 2
    assert s["by_name"]["d"]["payload_bytes"] == 15


def test_report_interleaves_all_kinds_in_submission_order():
    with TraceSession("rep") as sess:
        cs = sess.capture.lower_and_compile("h", lambda x: x - 1,
                                            args=(jnp.ones(2),))
        sess.wrap(cs.compiled, "h_disp")(jnp.ones(2))
        sess.mover.put(np.zeros(2, np.float32))
    assert [e.kind for e in sess.timeline()] == \
        ["compile", "dispatch", "transfer"]
    text = sess.report()
    event_lines = [l for l in text.splitlines()
                   if l.strip()[:1].isdigit() and "ms" in l]
    assert [l.split()[2] for l in event_lines] == \
        ["compile", "dispatch", "transfer"]
    assert "TRACE SESSION rep" in text


def test_empty_session_summary_is_wellformed_and_zeroed():
    """Regression: an untouched session must return the full documented
    schema with zeros, not whatever falls out of empty accumulators."""
    s = TraceSession("empty").summary()
    json.dumps(s)               # serializable
    kinds = {"compile", "dispatch", "transfer", "graph_launch", "progress"}
    assert s["events"] == 0 and s["dropped"] == 0
    assert s["by_kind"] == {k: 0 for k in kinds}
    assert s["dur_s_by_kind"] == {k: 0.0 for k in kinds}
    assert s["payload_by_kind"] == {k: 0 for k in kinds}
    assert s["by_name"] == {}
    assert s["total_payload_bytes"] == 0
    assert s["total_dispatch_s"] == 0.0
    assert s["wall_s"] >= 0.0
    assert s["session"] == "empty"
    # after the first event the per-kind maps track only what was seen
    sess = TraceSession("one")
    sess.emit("dispatch", "d")
    assert sess.summary()["by_kind"] == {"dispatch": 1}


def test_session_tags_land_in_every_event_meta():
    with TraceSession("tagged", tags={"host": "h0", "process": 3}) as sess:
        sess.emit("dispatch", "d")
        sess.emit("transfer", "t", mode="inline")   # explicit meta merges
        sess.emit("progress", "p", process=9)       # explicit wins
    evs = sess.timeline()
    assert all(e.meta["host"] == "h0" for e in evs)
    assert evs[0].meta["process"] == 3
    assert evs[1].meta == {"host": "h0", "process": 3, "mode": "inline"}
    assert evs[2].meta["process"] == 9


def test_session_barrier_emits_alignment_event():
    with TraceSession("b") as sess:
        ev = sess.barrier("sync-1")
    assert ev.kind == "progress" and ev.name == "obs.barrier"
    assert ev.meta["barrier"] == "sync-1"
    assert isinstance(ev.meta["wall"], float)


def test_sink_stats_one_entry_per_sink(tmp_path):
    class Bare:                 # sink without stats()
        def emit(self, e):
            pass

    path = str(tmp_path / "t.jsonl")
    sess = TraceSession("stats", jsonl_path=path, sinks=[Bare()])
    sess.emit("dispatch", "d")
    stats = sess.sink_stats()
    assert [s["sink"] for s in stats] == \
        ["RingBufferSink", "JsonlSink", "Bare"]
    assert stats[0]["emitted"] == 1
    assert stats[1]["written"] == 1


def test_add_and_remove_sink_midflight():
    sess = TraceSession("dyn")
    sess.emit("dispatch", "before")
    late = RingBufferSink()
    sess.add_sink(late)
    sess.emit("dispatch", "during")
    sess.remove_sink(late)
    sess.emit("dispatch", "after")
    assert [e.name for e in late.events()] == ["during"]


# -- thread safety ----------------------------------------------------------

def test_ring_buffer_sink_thread_safe_counts():
    """Satellite: drop-count updates must be exact when one ring is shared
    by several sessions emitting concurrently."""
    import threading

    ring = RingBufferSink(maxlen=64)
    sessions = [TraceSession(f"s{i}", sinks=[ring]) for i in range(4)]

    def pump(sess):
        for _ in range(500):
            sess.emit("progress", "p")

    threads = [threading.Thread(target=pump, args=(s,)) for s in sessions]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert ring.n_emitted == 2000
    assert len(ring) == 64
    assert ring.dropped == 2000 - 64
    st = ring.stats()
    assert st["emitted"] == 2000 and st["dropped"] == 2000 - 64


def test_emit_thread_safe_seq_and_jsonl(tmp_path):
    """A traffic thread and a decode loop share one session: sequence
    numbers stay unique/contiguous and the lazily-opened JSONL sink never
    double-opens or interleaves lines."""
    import threading

    path = tmp_path / "threads.jsonl"
    n_threads, per_thread = 8, 50
    with TraceSession("mt", jsonl_path=str(path)) as sess:
        barrier = threading.Barrier(n_threads)

        def worker(tid):
            barrier.wait()      # maximize interleaving incl. the lazy open
            for i in range(per_thread):
                sess.emit("progress", f"w{tid}", payload_bytes=1)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    total = n_threads * per_thread
    assert sess.n_events == total
    seqs = [e.seq for e in sess.timeline()]
    assert seqs == list(range(total))               # unique AND contiguous
    loaded = JsonlSink.load(str(path))              # every line parses
    assert len(loaded) == total
    assert sorted(e.seq for e in loaded) == list(range(total))
    s = sess.summary()
    assert s["by_kind"]["progress"] == total
    assert s["total_payload_bytes"] == total


def test_jsonl_sink_shared_across_sessions_single_file_handle(tmp_path):
    """One sink instance fed by two sessions concurrently stays consistent."""
    import threading

    path = tmp_path / "shared.jsonl"
    sink = JsonlSink(str(path))
    a = TraceSession("a", sinks=[sink])
    b = TraceSession("b", sinks=[sink])

    def pump(sess):
        for _ in range(100):
            sess.emit("dispatch", "x")

    ts = [threading.Thread(target=pump, args=(s,)) for s in (a, b)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    sink.close()
    assert len(JsonlSink.load(str(path))) == 200


def test_jsonl_load_skips_hand_truncated_trailing_line(tmp_path):
    """A shard whose last line was cut mid-write (killed process) still
    loads: every complete line parses, the partial one is skipped with a
    warning.  Corruption *before* valid lines is a broken file and raises.
    """
    path = tmp_path / "crashed.jsonl"
    with TraceSession("victim", jsonl_path=str(path)) as sess:
        for i in range(5):
            sess.emit("dispatch", f"d{i}", payload_bytes=8 * i)
    full = path.read_text()
    lines = full.splitlines(keepends=True)
    # chop the final record in half, as a SIGKILL mid-fwrite would
    path.write_text("".join(lines[:-1]) + lines[-1][: len(lines[-1]) // 2])

    with pytest.warns(RuntimeWarning, match="truncated trailing line"):
        loaded = JsonlSink.load(str(path))
    assert [e.name for e in loaded] == [f"d{i}" for i in range(4)]
    assert all(e.payload_bytes == 8 * e.seq for e in loaded)

    # same half-line *followed by* valid records is not a crash artifact
    path.write_text("".join(lines[:3])
                    + lines[3][: len(lines[3]) // 2] + "\n" + lines[4])
    with pytest.raises((json.JSONDecodeError, KeyError, ValueError)):
        JsonlSink.load(str(path))
