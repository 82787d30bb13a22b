"""Innermost-span sweep over the program's own spans.

A copy of the arithmetic of ``repro.core.trace.stall_report`` (kept here so
that no later change to the program can change how the benchmark reads its
spans), generalised to every root span and not only the last one. Every
instant of a root's wall goes to the innermost open span on the root's
thread; ``compute`` takes what no child covers, so the categories sum to
the wall.
"""

from __future__ import annotations

CATEGORIES = ("compute", "d2h", "stage_wait", "level0_write", "level1_flush",
              "remote_put", "remote_get", "barrier")

_D2H_NAMES = {"snapshot", "extract", "gather", "h2d", "d2h"}
_WAIT_NAMES = {"budget.wait", "read.stall", "stage.wait", "acquire.wait"}


def category(ev) -> str | None:
    n = ev.name
    if "barrier" in n:
        return "barrier"
    if n in _WAIT_NAMES:
        return "stage_wait"
    if n in _D2H_NAMES:
        return "d2h"
    if ev.tier == "remote":
        return "remote_put" if ("put" in n or "upload" in n) else "remote_get"
    if ev.tier == "level1":
        return "level1_flush"
    if ev.tier == "level0":
        return "level0_write"
    return None


def attribute(events, root) -> dict[str, float]:
    """Seconds of ``root``'s wall per category (sums to the wall)."""
    inner = [e for e in events
             if e.kind == "span" and e.tid == root.tid
             and e.span_id != root.span_id
             and e.t1 > root.t0 and e.t0 < root.t1]
    marks = []
    for e in inner:
        marks.append((max(e.t0, root.t0), 1, e))
        marks.append((min(e.t1, root.t1), -1, e))
    marks.sort(key=lambda m: (m[0], -m[1]))
    out = {c: 0.0 for c in CATEGORIES}
    open_spans: dict[int, object] = {}
    prev = root.t0
    for t, delta, e in marks:
        if t > prev:
            if open_spans:
                top = max(open_spans.values(),
                          key=lambda s: (s.t0, s.span_id))
                out[category(top) or "compute"] += t - prev
            else:
                out["compute"] += t - prev
            prev = t
        if delta > 0:
            open_spans[e.span_id] = e
        else:
            open_spans.pop(e.span_id, None)
    if root.t1 > prev:
        out["compute"] += root.t1 - prev
    return out


def level0_io(ev) -> bool:
    """A write or a sync of the level-0 engine, on whatever thread."""
    return ev.tier == "level0" and ev.name.startswith("io.")


def covered(events, root, keep) -> float:
    """Seconds of ``root``'s wall during which at least one span for which
    ``keep(span)`` holds was open, on any thread: the union of those spans'
    intervals, clipped to the root."""
    iv = sorted((max(e.t0, root.t0), min(e.t1, root.t1)) for e in events
                if e.kind == "span" and e.span_id != root.span_id and keep(e)
                and e.t1 > root.t0 and e.t0 < root.t1)
    total, end = 0.0, root.t0
    for a, b in iv:
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def roots(events, name: str, t0: float | None = None,
          t1: float | None = None) -> list:
    """Spans called ``name`` that start inside [t0, t1) (all when open)."""
    return [e for e in events if e.kind == "span" and e.name == name
            and (t0 is None or e.t0 >= t0) and (t1 is None or e.t0 < t1)]
