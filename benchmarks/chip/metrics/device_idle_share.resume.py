"""Share of the traced window in which no operation ran on the device,
in a resume cell (1 - union of op intervals / window), in percent."""


def read(run):
    if run.device_trace is None or not run.resume_s:
        return None
    return 100.0 * run.device_trace.idle_share
