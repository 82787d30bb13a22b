"""Process start to the measured window's start: imports, device and
compile-cache start-up, making the state, compiling or loading every
program, the three checked steps and any warm-up (host clock)."""


def read(run):
    return run.setup_s
