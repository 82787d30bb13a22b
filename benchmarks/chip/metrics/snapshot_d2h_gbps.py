"""Bytes the window's saves copied device to host, over the time of their
``snapshot`` spans (the pipeline's resolve of each shard: the D2H copy),
in GB/s. Read from the program's spans in the traced run."""

from chipbench import spans


def read(run):
    t0, t1 = run.span_window
    nbytes = dur = 0.0
    for root in spans.roots(run.spans, "save", t0, t1):
        for e in run.spans:
            if (e.kind == "span" and e.name == "snapshot"
                    and e.tid == root.tid and root.t0 <= e.t0 < root.t1):
                nbytes += e.nbytes
                dur += e.t1 - e.t0
    if dur <= 0:
        return None
    return nbytes / dur / 1e9
