"""core.trace — span nesting/parenting across threads, the disabled-mode
no-op fast path, drop-oldest ring overflow, Perfetto export round-trips,
the spans that name a save's and a restore's host work, and the ``ckpt.*``
profiler annotations that put the spans on the device trace's clock
(DESIGN.md §17)."""

import glob
import json
import os
import threading
import time

import numpy as np
import pytest

from repro.core import CheckpointManager, trace
from repro.core.engines import EngineConfig


@pytest.fixture(autouse=True)
def _fresh_tracer():
    trace.disable()
    yield
    trace.disable()


# ------------------------------------------------------------------ spans
def test_span_nesting_and_parenting_across_threads():
    trace.enable()
    with trace.span("outer"):
        with trace.span("inner"):
            pass

    def worker():
        with trace.span("outer_t2"):
            with trace.span("inner_t2"):
                pass

    th = threading.Thread(target=worker, name="trace-worker")
    th.start()
    th.join()
    by = {e.name: e for e in trace.drain()}
    assert by["inner"].parent_id == by["outer"].span_id
    assert by["outer"].parent_id == 0
    # each thread keeps its own stack: no cross-thread auto-parenting
    assert by["outer_t2"].parent_id == 0
    assert by["inner_t2"].parent_id == by["outer_t2"].span_id
    assert by["inner_t2"].tid != by["inner"].tid
    assert by["inner_t2"].thread == "trace-worker"
    # timestamps nest
    assert by["outer"].t0 <= by["inner"].t0 <= by["inner"].t1 <= by["outer"].t1


def test_explicit_parent_links_across_threads():
    trace.enable()
    with trace.span("root") as root:
        root_id = root.id

        def worker():
            with trace.span("cross", parent=root_id):
                pass

        th = threading.Thread(target=worker)
        th.start()
        th.join()
    by = {e.name: e for e in trace.drain()}
    assert by["cross"].parent_id == root_id


def test_complete_records_pre_timed_span():
    trace.enable()
    t0 = trace.clock()
    time.sleep(0.001)
    trace.complete("io.write", t0, tier="level0", nbytes=4096)
    (ev,) = trace.drain()
    assert ev.name == "io.write" and ev.tier == "level0"
    assert ev.nbytes == 4096 and ev.t1 >= ev.t0 == t0


# ------------------------------------------------------ disabled fast path
def test_disabled_fast_path_is_shared_noop():
    assert not trace.is_enabled()
    s1 = trace.span("a", tier="level0", nbytes=123)
    s2 = trace.span("b")
    # one shared singleton: the disabled path allocates nothing per call
    assert s1 is s2 is trace._NOOP
    with s1:
        pass
    trace.event("x", attrs={"k": "v"})
    trace.count("c", 2.0)
    trace.complete("y", 0.0, 1.0)
    assert trace.drain() == []
    assert trace.dropped_events() == 0
    assert trace.stall_report(root="save") is None


# ------------------------------------------------------------ ring overflow
def test_ring_overflow_drops_oldest_with_counter():
    trace.enable(capacity=8)
    for i in range(20):
        trace.event(f"e{i}")
    evs = trace.drain()
    assert [e.name for e in evs] == [f"e{i}" for i in range(12, 20)]
    assert trace.dropped_events() == 12
    # drops are per-thread: a fresh thread's ring starts clean
    def worker():
        trace.event("t2")
    th = threading.Thread(target=worker)
    th.start()
    th.join()
    assert trace.dropped_events() == 12
    assert any(e.name == "t2" for e in trace.drain())


# ---------------------------------------------------------- perfetto export
def test_perfetto_export_round_trips(tmp_path):
    trace.enable()
    with trace.span("save", tier="host", nbytes=96 << 20,
                    attrs={"step": 7}):
        with trace.span("flush", tier="level0"):
            trace.event("hedge.issue", tier="level1",
                        attrs={"path": "data.bin"})
    path = tmp_path / "trace.json"
    trace.export_perfetto(str(path))
    doc = json.loads(path.read_text())
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in xs} == {"save", "flush"}
    by = {e["name"]: e for e in xs}
    # microsecond timestamps, monotonically consistent nesting
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0
    assert by["save"]["ts"] <= by["flush"]["ts"]
    assert (by["flush"]["ts"] + by["flush"]["dur"]
            <= by["save"]["ts"] + by["save"]["dur"] + 1.0)
    assert by["save"]["args"]["step"] == 7
    assert by["save"]["args"]["bytes"] == 96 << 20
    # spans land on tier-named tracks; instants ride along
    procs = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("name") == "process_name"}
    assert {"tier:host", "tier:level0", "tier:level1"} <= procs
    insts = [e for e in doc["traceEvents"] if e.get("ph") == "i"]
    assert [e["name"] for e in insts] == ["hedge.issue"]
    assert insts[0]["args"]["path"] == "data.bin"


def test_prometheus_export_textfile(tmp_path):
    trace.enable()
    trace.count("faults_injected", 3)
    with trace.span("flush", tier="level0"):
        pass
    text = trace.export_prometheus(str(tmp_path / "metrics.prom"))
    assert (tmp_path / "metrics.prom").read_text() == text
    assert "crtrace_faults_injected 3" in text
    assert "crtrace_trace_dropped_events 0" in text
    assert 'crtrace_span_seconds_flush_bucket{tier="level0",le="+Inf"} 1' \
        in text
    assert "crtrace_span_seconds_flush_count" in text


# ------------------------------------------------------------- stall report
def test_stall_report_attribution_sums_to_wall():
    trace.enable()
    with trace.span("save", nbytes=1 << 20):
        with trace.span("extract"):           # d2h
            time.sleep(0.004)
        with trace.span("fingerprint"):       # uncategorized -> compute
            time.sleep(0.002)
        with trace.span("flush", tier="level0"):
            with trace.span("budget.wait"):   # stage wait inside the flush
                time.sleep(0.002)
            time.sleep(0.004)
    rep = trace.stall_report(root="save")
    assert rep is not None
    assert set(rep.attribution) == set(trace.CATEGORIES)
    assert sum(rep.attribution.values()) == pytest.approx(rep.wall, rel=1e-6)
    assert rep.attribution["d2h"] >= 0.003
    assert rep.attribution["stage_wait"] >= 0.001
    # the nested wait is NOT double-counted into the flush
    assert rep.attribution["level0_write"] >= 0.003
    assert rep.wall >= 0.011
    out = rep.render()
    assert "top bottleneck" in out and "save" in out


# --------------------------------------------------- host work of save/restore
def _spans(events, name, root=None):
    return [e for e in events if e.kind == "span" and e.name == name
            and (root is None or root.t0 <= e.t0 <= e.t1 <= root.t1)]


def test_save_and_restore_name_their_host_work(tmp_path):
    """A streaming save's ``crc`` and ``stage.copy`` spans carry every byte
    of its payload; a restore's ``read.land`` spans every byte it read that
    did not land straight in the array ``get`` returns (``read.direct_bytes``
    counts the rest), and its ``read.wait``, ``read.land`` and ``crc`` spans
    lie inside the ``restore`` span on the restore's own thread."""
    state = {"big": np.arange(1 << 20, dtype=np.float32),   # 4 chunks
             "small": np.ones((8, 128), np.float32),
             "tiny": np.zeros((128,), np.float32), "step": 3}
    mgr = CheckpointManager(
        str(tmp_path / "ck"),
        config=EngineConfig(backend="threadpool", chunk_bytes=1 << 20,
                            inflight_bytes=4 << 20))
    trace.enable()
    try:
        sm = mgr.save(1, state)
        out = mgr.restore()
        rm = mgr.last_restore_metrics
        counters = trace.active().counters()
        events = trace.drain()
    finally:
        trace.disable()
        mgr.close()
    np.testing.assert_array_equal(out["big"], state["big"])
    (save,) = _spans(events, "save")
    (restore,) = _spans(events, "restore")
    copies = _spans(events, "stage.copy", save)
    assert len(copies) >= 4
    assert sum(e.nbytes for e in copies) == sm.total_bytes
    assert sum(e.nbytes for e in _spans(events, "crc", save)) == \
        sm.total_bytes
    # the restore reads the lean blob and every tensor, and CRCs the tensors;
    # an extent read alone ("big") lands in place with no copy; "small" and
    # "tiny" share a coalesced read, copied out of its pooled buffer
    assert rm.direct_bytes == state["big"].nbytes
    assert counters["read.direct_bytes"] >= rm.direct_bytes
    assert sum(e.nbytes for e in _spans(events, "read.land", restore)) + \
        counters["read.direct_bytes"] == sm.total_bytes
    assert sum(e.nbytes for e in _spans(events, "crc", restore)) == \
        rm.total_bytes
    assert _spans(events, "read.wait")
    for name in ("read.wait", "read.land", "crc"):
        inside = [e for e in _spans(events, name) if e.t0 >= restore.t0]
        assert inside and all(e.tid == restore.tid and e.t1 <= restore.t1
                              for e in inside), name


def test_snapshot_wait_only_while_a_save_is_in_flight(tmp_path):
    state = {"w": np.arange(1 << 16, dtype=np.float32), "step": 1}
    mgr = CheckpointManager(str(tmp_path / "ck"), async_save=True)
    begin = mgr.engine.begin_save

    def slow_begin(*a, **k):      # hold the pipeline before it stages
        time.sleep(0.05)
        return begin(*a, **k)

    mgr.engine.begin_save = slow_begin
    trace.enable()
    try:
        mgr.wait_snapshotted()            # no save: nothing to wait for
        sm = mgr.save(1, state)
        mgr.wait_snapshotted()            # staging still under way
        mgr.wait()
        mgr.wait_snapshotted()            # committed: nothing in flight
        events = trace.drain()
    finally:
        trace.disable()
        mgr.close()
    (wait,) = _spans(events, "snapshot.wait")
    (save,) = _spans(events, "save")
    assert wait.tid == threading.get_ident() != save.tid
    assert wait.nbytes == sm.total_bytes
    assert wait.t1 - wait.t0 >= 0.03


# ------------------------------------------------------ the profiler's clock
class _Marks:
    """Annotation factory that records (thread, name, enter/exit)."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        marks = self

        class _Ann:
            def __enter__(self):
                marks.log.append((threading.get_ident(), name, "enter",
                                  trace.clock()))

            def __exit__(self, *exc):
                marks.log.append((threading.get_ident(), name, "exit",
                                  trace.clock()))
        return _Ann()


@pytest.fixture
def marks():
    m = _Marks()
    saved = trace._ANNOTATION_FACTORY
    trace.set_annotation_factory(m)
    yield m
    trace.set_annotation_factory(saved)


def test_annotated_span_opens_and_closes_with_its_event(marks):
    assert trace.span("save", annotate=True) is trace._NOOP
    with trace.span("save", annotate=True):
        pass
    assert marks.log == []                # disabled: the hook is untouched
    trace.enable()
    with trace.span("save", annotate=True):
        with trace.span("snapshot"):
            pass
    (ev,) = _spans(trace.drain(), "save")
    (t_in, n_in, k_in, c_in), (t_out, n_out, k_out, c_out) = marks.log
    assert (n_in, k_in, n_out, k_out) == ("ckpt.save", "enter", "ckpt.save",
                                          "exit")
    assert t_in == t_out == ev.tid
    assert c_in <= ev.t0 <= ev.t1 <= c_out


def _ev(name, t0, t1=None):
    return trace.TraceEvent("span", name, "host", t0,
                            t0 + 1.0 if t1 is None else t1, 0, 0, 0, 1,
                            "main", None)


def test_profiler_offset_pairs_annotations_with_their_spans():
    # spans before and after the profiled part have no annotation; one
    # annotation has no span (dropped), another name is not ours
    events = [_ev("save", 1.0), _ev("snapshot.wait", 1.5), _ev("save", 11.0),
              _ev("snapshot.wait", 11.4), _ev("save", 21.0),
              _ev("restore", 30.0), _ev("io.write", 11.0)]
    off = 5e9
    anns = [("ckpt.save", 11.0e9 + off + 3e3),
            ("ckpt.snapshot.wait", 11.4e9 + off - 2e3),
            ("ckpt.save", 21.0e9 + off + 1e3),
            ("ckpt.restore", 30.0e9 + off),
            ("ckpt.save", 40.0e9 + off),
            ("bench.save", 11.0e9 + off)]
    got, worst = trace.profiler_offset(events, anns)
    assert got == pytest.approx(off + 1e3, abs=1.0)
    assert worst == pytest.approx(3e3, abs=1.0)
    with pytest.raises(ValueError):
        trace.profiler_offset(events, [("bench.save", 1.0)])


def test_profiler_trace_holds_ckpt_annotations_on_the_span_clock(tmp_path):
    """On the CPU's profiler, ``ckpt.save`` (on the save's own new thread),
    ``ckpt.snapshot.wait`` and ``ckpt.restore`` start and end with their
    spans, within 50 us after one offset."""
    import jax
    from jax.profiler import ProfileData
    state = {"w": np.arange(1 << 20, dtype=np.float32), "step": 1}
    mgr = CheckpointManager(str(tmp_path / "ck"), async_save=True)
    trace.enable()
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        for step in (1, 2):
            mgr.save(step, state)
            mgr.wait_snapshotted()
            mgr.wait()
            mgr.restore(step=step)
    finally:
        jax.profiler.stop_trace()
        events = trace.drain()
        trace.disable()
        mgr.close()
    (path,) = glob.glob(os.path.join(str(tmp_path / "prof"), "**",
                                     "*.xplane.pb"), recursive=True)
    anns = [(ev.name, int(ev.start_ns), int(ev.end_ns))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("ckpt.")]
    names = sorted(a[0] for a in anns)
    assert names.count("ckpt.save") == names.count("ckpt.restore") == 2
    assert set(names) <= {"ckpt.save", "ckpt.restore", "ckpt.snapshot.wait"}
    off, worst = trace.profiler_offset(events, [a[:2] for a in anns])
    assert worst < 50e3
    for name, a0, a1 in anns:
        ev = min(_spans(events, name[len("ckpt."):]),
                 key=lambda e: abs(e.t0 * 1e9 + off - a0))
        assert abs(ev.t0 * 1e9 + off - a0) < 50e3
        assert abs(ev.t1 * 1e9 + off - a1) < 50e3
