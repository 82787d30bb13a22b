"""Mean per restore of the time spent copying landed reads out of the pooled
read buffers, in seconds: for each ``restore`` span started in the traced
window, the union over every thread of the ``read.land`` spans, clipped to
the restore's span."""

from chipbench.spanwork import mean_covered


def read(run):
    return mean_covered(run, "restore", "read.land")
