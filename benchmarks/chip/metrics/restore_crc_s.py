"""Mean per restore of the time the restored bytes were being CRC'd, in
seconds: for each ``restore`` span started in the traced window, the union
over every thread of the ``crc`` spans, clipped to the restore's span."""

from chipbench.spanwork import mean_covered


def read(run):
    return mean_covered(run, "restore", "crc")
