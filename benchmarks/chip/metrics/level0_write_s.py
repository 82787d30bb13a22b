"""Mean per save of the time in which the level-0 engine had a write or a
sync in flight, in seconds: for each ``save`` span started in the traced
window, the union over every thread of the ``io.*`` spans of the
``level0`` tier, clipped to the save's span (``chipbench.spans.covered``).
Under the ``threadpool`` backend those spans run on the pool's threads and
under ``uring`` on the reaper's, not on the save's own thread."""

from chipbench import spans


def read(run):
    t0, t1 = run.span_window
    roots = spans.roots(run.spans, "save", t0, t1)
    if not roots:
        return None
    return sum(spans.covered(run.spans, r, spans.level0_io)
               for r in roots) / len(roots)
