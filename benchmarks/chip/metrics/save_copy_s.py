"""Mean per save of the time spent copying the payload into pooled staging
buffers, in seconds: for each ``save`` span started in the traced window,
the union over every thread of the ``stage.copy`` spans, clipped to the
save's span."""

from chipbench.spanwork import mean_covered


def read(run):
    return mean_covered(run, "save", "stage.copy")
