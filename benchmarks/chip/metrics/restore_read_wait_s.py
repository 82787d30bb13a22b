"""Mean per restore of the time the restore waited on the disk alone, in
seconds: for each ``restore`` span started in the traced window, the union
over every thread of the ``read.wait`` spans (the reader blocked in the I/O
backend's poll), clipped to the restore's span."""

from chipbench.spanwork import mean_covered


def read(run):
    return mean_covered(run, "restore", "read.wait")
