"""Mean over the window's resumes of the time from constructing a fresh
``Trainer`` to its first step after ``Trainer.resume`` being ready on the
device (host clock). Every resume started in the window is counted."""


def read(run):
    if not run.resume_s:
        return None
    return sum(run.resume_s) / len(run.resume_s)
