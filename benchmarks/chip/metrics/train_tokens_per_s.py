"""Tokens of every step completed in the window over the window's length,
stalls for saves included (host clock)."""


def read(run):
    if not run.steps or run.window_s <= 0:
        return None
    return run.steps * run.tokens_per_step / run.window_s
