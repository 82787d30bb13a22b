"""Time a kind of host work took inside each save or restore.

For every root span (``save`` or ``restore``) started in the traced window,
the union over every thread of the spans of one name, clipped to the root
(``spans.covered``); the mean over the roots, in seconds. A union over
threads keeps reading the work if it moves off the root's own thread.
"""

from __future__ import annotations

from chipbench import spans


def mean_covered(run, root: str, name: str):
    """Mean over the window's ``root`` spans of the seconds covered by
    spans called ``name``; None when the window has no such root, or the
    program records no such span (it is older than the span)."""
    roots = spans.roots(run.spans, root, *run.span_window)
    if not roots or not any(e.kind == "span" and e.name == name
                            for e in run.spans):
        return None
    return sum(spans.covered(run.spans, r, lambda e: e.name == name)
               for r in roots) / len(roots)
