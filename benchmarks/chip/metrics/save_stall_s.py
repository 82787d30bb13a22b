"""Loop time spent in ``save()`` and ``wait_snapshotted()`` in the window,
over the saves started in it (host clock). ``save()`` includes its own wait
on the previous save's flush."""


def read(run):
    if not run.saves:
        return None
    return run.stall_s / len(run.saves)
