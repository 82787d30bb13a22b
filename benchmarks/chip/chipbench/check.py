"""The comparison that decides ``correct``.

Training (every cell): the program's first three steps against the plain
reference's, from the same seeded weights and tokens. The configuration's
``limits`` name which of these numbers are compared.
  * ``loss_gap``: the largest of the three steps' |loss - reference loss|
    over the reference loss (``loss1_gap``: the first step's alone);
  * ``grad_gap`` / ``grad_median_gap``: the worst / median leaf's gap
    between the norms of the first gradient as AdamW took it;
  * ``update_gap`` / ``update_median_gap``: the worst / median leaf's gap
    between the norms of the params' change after three steps.
A leaf's gap is taken against the larger of its own reference norm and the
median leaf's. Leaves whose first reference gradient is under a thousandth
of the median leaf's move under AdamW by round-off alone; both gaps leave
them out (listed in ``left_out``).

Checkpoints (bit for bit, limit 0): every state restored from a save made
in the run has the fingerprint of the state that was saved.
"""

from __future__ import annotations

import math

import numpy as np

SMALL_GRAD = 1e-3


def leaf_gaps(prog: np.ndarray, ref: np.ndarray,
              mask: np.ndarray) -> np.ndarray:
    """Each compared leaf's gap (NaN where a leaf is left out)."""
    med = float(np.median(ref[mask])) if mask.any() else 0.0
    denom = np.maximum(ref, med)
    gap = np.abs(prog - ref) / np.where(denom > 0, denom, 1.0)
    return np.where(mask, gap, np.nan)


def _worst(gaps: np.ndarray) -> tuple[float, int]:
    if np.all(np.isnan(gaps)):
        return math.inf, -1
    i = int(np.nanargmax(gaps))
    return float(gaps[i]), i


def training_gaps(prog, ref) -> dict[str, float]:
    """``prog`` and ``ref`` are ``reference.Readings``-like."""
    losses = [abs(a - b) / abs(b) for a, b in zip(prog.losses, ref.losses)]
    if len(losses) != len(ref.losses) or not all(map(math.isfinite, losses)):
        loss_gap = math.inf
    else:
        loss_gap = max(losses)
    g_ref = np.asarray(ref.grad_norms, np.float64)
    mask = g_ref >= SMALL_GRAD * np.median(g_ref)
    g_gaps = leaf_gaps(np.asarray(prog.grad_norms, np.float64), g_ref, mask)
    u_gaps = leaf_gaps(np.asarray(prog.update_norms, np.float64),
                       np.asarray(ref.update_norms, np.float64), mask)
    grad, gi = _worst(g_gaps)
    upd, ui = _worst(u_gaps)
    grad_med = float(np.nanmedian(g_gaps)) if mask.any() else math.inf
    upd_med = float(np.nanmedian(u_gaps)) if mask.any() else math.inf
    loss1 = (abs(prog.losses[0] - ref.losses[0]) / abs(ref.losses[0])
             if prog.losses else math.inf)
    if not (np.all(np.isfinite(prog.grad_norms))
            and np.all(np.isfinite(prog.update_norms))):
        grad = upd = grad_med = upd_med = math.inf
    if not math.isfinite(loss1):
        loss1 = math.inf
    return {"loss_gap": loss_gap, "loss1_gap": loss1,
            "grad_gap": grad, "grad_median_gap": grad_med,
            "update_gap": upd, "update_median_gap": upd_med,
            "grad_worst": gi, "update_worst": ui,
            "left_out": [int(i) for i in np.flatnonzero(~mask)]}


NUMBERS = ("loss_gap", "loss1_gap", "grad_gap", "grad_median_gap",
           "update_gap", "update_median_gap")


def limit_checks(gaps: dict, limits: dict, checks=None):
    """``checks`` (a new ``Checks`` when None) with each number that
    ``limits`` names beside its limit."""
    checks = Checks() if checks is None else checks
    for name, limit in limits.items():
        checks.add(name, gaps[name], limit)
    return checks


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


class Checks:
    """Numbers compared, each beside its limit; all must be at or under."""

    def __init__(self):
        self.items: dict[str, list] = {}

    def add(self, name: str, value, limit) -> None:
        self.items[name] = [value, limit]

    @property
    def correct(self) -> bool:
        return bool(self.items) and all(
            _finite(v) and v <= lim for v, lim in self.items.values())
