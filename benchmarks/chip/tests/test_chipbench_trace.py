"""The reductions from traces to metrics: device busy and idle time with
named gaps, and the copied innermost-span sweep."""

import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

from _util import CHIP  # noqa: F401  (puts the benchmark on sys.path)
from chipbench import devtrace, spans, spec


def ev(name, t0, t1):
    return NS(name=name, start_ns=t0, end_ns=t1, duration_ns=t1 - t0)


def recorded_planes():
    """A window of 1000 ns: ops busy 100-300 and 250-400 (overlapping) and
    700-800; the host saves 400-700 and dispatches 800-900."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 0, 2000), ev("bench.traced", 0, 1000), ev("bench.save", 400, 700),
        ev("bench.dispatch", 800, 900), ev("bench.dispatch", 50, 60)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("%fusion.1 = f32[8] fusion(x)", 100,
                                      300),
                                   ev("fusion.2", 250, 400),
                                   ev("%fusion.1 = f32[8] fusion(x)", 700,
                                      800),
                                   ev("copy.3", 1100, 1200)]),
        NS(name="XLA Modules", events=[ev("jit_train_step(7)", 100, 400),
                                       ev("jit_train_step(7)", 700, 800),
                                       ev("jit_other(2)", 1100, 1200)])])
    sparse = NS(name="/device:TPU:0 SparseCore 0", lines=[])
    return [host, dev, sparse]


def test_busy_idle_and_gaps():
    dt = devtrace.reduce_planes(recorded_planes())
    assert dt.devices == 1
    assert dt.window_s == pytest.approx(1000e-9)
    assert dt.busy_s == pytest.approx(400e-9)      # 100-400 and 700-800
    assert dt.idle_share == pytest.approx(0.6)
    assert list(dt.module_s) == ["jit_train_step"]
    assert dt.module_s["jit_train_step"] == pytest.approx([300e-9, 100e-9])
    assert dt.top_ops[0] == ["fusion.1", pytest.approx(300e-9)]
    names = [g[0] for g in dt.idle_gaps]
    secs = [g[1] for g in dt.idle_gaps]
    # gaps 400-700 (mid 550: save), 800-1000 (mid 900: no host span),
    # 0-100 (mid 50: dispatch)
    assert names == ["save", "host_idle", "dispatch"]
    assert secs == pytest.approx([300e-9, 200e-9, 100e-9])
    assert sorted(secs, reverse=True) == secs
    assert pytest.approx(sum(secs)) == 600e-9


def test_union():
    assert devtrace.union([(5, 9), (1, 3), (2, 4), (9, 10)]) == [(1, 4),
                                                                (5, 10)]


def test_trace_without_window_is_refused():
    planes = recorded_planes()
    planes[0].lines[0].events = [e for e in planes[0].lines[0].events
                                 if e.name != "bench.traced"]
    with pytest.raises(ValueError):
        devtrace.reduce_planes(planes)


def test_span_sweep_matches_stall_report(tmp_path):
    """On a recorded save, the copied sweep gives what the program's own
    ``trace.stall_report`` gives."""
    from repro.core import CheckpointManager, trace
    state = {"w": np.arange(1 << 18, dtype=np.float32),
             "v": np.ones((64, 1024), np.float32), "step": 3}
    trace.enable()
    try:
        mgr = CheckpointManager(str(tmp_path / "ck"), async_save=True)
        mgr.save(1, state)
        mgr.wait()
        mgr.save(2, state)
        mgr.wait()
        mgr.close()
        events = trace.drain()
    finally:
        trace.disable()
    roots = spans.roots(events, "save")
    assert len(roots) == 2
    want = trace.stall_report(events, root="save")
    got = spans.attribute(events, roots[-1])
    assert got == pytest.approx(want.attribution)
    assert sum(got.values()) == pytest.approx(roots[-1].t1 - roots[-1].t0)


def span(name, t0, t1, tid, tier="", sid=0):
    return NS(kind="span", name=name, tier=tier, t0=t0, t1=t1, tid=tid,
              span_id=sid)


def test_covered_unions_threads_within_the_root():
    root = span("save", 10.0, 20.0, tid=1, sid=1)
    events = [root,
              span("io.write", 8.0, 12.0, tid=2, tier="level0", sid=2),
              span("io.write", 11.0, 13.0, tid=3, tier="level0", sid=3),
              span("io.write", 15.0, 16.0, tid=2, tier="level0", sid=4),
              span("io.fsync", 19.0, 25.0, tid=1, tier="level0", sid=5),
              span("snapshot", 12.0, 18.0, tid=1, sid=6)]
    io = [e for e in events if e.tier == "level0"]
    assert spans.covered(events, root, lambda e: e in io) == \
        pytest.approx(3.0 + 1.0 + 1.0)     # 10-13, 15-16, 19-20


def test_level0_write_reads_the_pool_threads(tmp_path):
    """Under the threadpool backend the writes run on the pool's threads;
    the reader counts them, where the sweep of the save's own thread sees
    only its waits."""
    from repro.core import CheckpointManager, trace
    from repro.core.engines import EngineConfig
    state = {"w": np.arange(1 << 20, dtype=np.float32), "step": 3}
    trace.enable()
    try:
        mgr = CheckpointManager(str(tmp_path / "ck"), async_save=True,
                                config=EngineConfig(backend="threadpool"))
        mgr.save(1, state)
        mgr.wait()
        mgr.close()
        events = trace.drain()
    finally:
        trace.disable()
    (root,) = spans.roots(events, "save")
    writes = [e for e in events if e.kind == "span" and e.name == "io.write"]
    assert writes and all(e.tid != root.tid for e in writes)
    run = NS(spans=events, span_window=(root.t0, root.t1 + 1))
    got = spec.metric_reader("level0_write_s").read(run)
    assert max(e.t1 - e.t0 for e in writes) <= got <= root.t1 - root.t0
