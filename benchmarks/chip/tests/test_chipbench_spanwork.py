"""The readers of the host work inside each save and restore: the union
over threads of one kind of span, clipped to each root, mean over roots."""

from types import SimpleNamespace as NS

import pytest

from _util import CHIP  # noqa: F401  (puts the benchmark on sys.path)
from chipbench import spec


def span(name, t0, t1, tid, sid):
    return NS(kind="span", name=name, tier="host", t0=t0, t1=t1, tid=tid,
              span_id=sid)


@pytest.mark.parametrize("metric,root,name", [
    ("save_crc_s", "save", "crc"),
    ("save_copy_s", "save", "stage.copy"),
    ("restore_read_wait_s", "restore", "read.wait"),
    ("restore_copy_s", "restore", "read.land"),
    ("restore_crc_s", "restore", "crc"),
])
def test_reader_unions_threads_per_root(metric, root, name):
    other = "stage.copy" if name == "crc" else "crc"
    events = [span(root, 10.0, 20.0, tid=1, sid=1),
              span(name, 12.0, 14.0, tid=1, sid=2),
              span(name, 13.0, 15.0, tid=2, sid=3),    # another thread
              span(name, 19.0, 21.0, tid=2, sid=4),    # clipped at 20
              span(other, 15.0, 19.0, tid=1, sid=5),   # not this work
              span(root, 30.0, 40.0, tid=3, sid=6),
              span(name, 31.0, 32.0, tid=2, sid=7),
              span(root, 50.0, 60.0, tid=1, sid=8),    # after the window
              span(name, 50.0, 60.0, tid=1, sid=9)]
    reader = spec.metric_reader(metric)
    run = NS(spans=events, span_window=(5.0, 45.0))
    # (12-15 and 19-20) and 31-32 over the window's two roots
    assert reader.read(run) == pytest.approx((4.0 + 1.0) / 2)
    # a program without the span, or a window without the root: no reading
    run.spans = [e for e in events if e.name != name]
    assert reader.read(run) is None
    run.spans, run.span_window = events, (45.0, 48.0)
    assert reader.read(run) is None
