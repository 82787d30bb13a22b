"""Bytes restored over the read stage's wall span, summed over the
window's resumes (``RestoreMetrics.total_bytes`` / ``read_seconds``),
in GB/s."""


def read(run):
    rms = [r for r in run.restores if r is not None]
    secs = sum(r.read_seconds for r in rms)
    if secs <= 0:
        return None
    return sum(r.total_bytes for r in rms) / secs / 1e9
