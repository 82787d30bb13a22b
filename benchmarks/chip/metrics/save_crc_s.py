"""Mean per save of the time the save's payload was being CRC'd, in
seconds: for each ``save`` span started in the traced window, the union over
every thread of the ``crc`` spans (``zlib.crc32`` over each put), clipped to
the save's span."""

from chipbench.spanwork import mean_covered


def read(run):
    return mean_covered(run, "save", "crc")
