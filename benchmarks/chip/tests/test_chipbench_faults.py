"""Runs with the timed path broken underneath come out not correct.

Two of the faults that a one-chip training cell can have, planted in the
program at the small size: a step that returns its state unchanged, and a
step that takes the mean over half of its batch. (The third, an answer
altered where it is produced, is in ``test_chipbench_altered.py``.)"""

import pytest

from _util import run_small

CELLS = ["stablelm-3b-8l.save_paced", "stablelm-3b-8l.resume"]


def unchanged_step(cfg, opt):
    from repro.train.steps import make_train_step
    real = make_train_step(cfg, opt)

    def step(state, batch):
        _, metrics = real(state, batch)
        return state, metrics
    return step


def half_batch_step(cfg, opt):
    from repro.train.steps import make_train_step
    real = make_train_step(cfg, opt)

    def step(state, batch):
        B, S = batch["tokens"].shape
        if B >= 2:
            half = {k: v[:B // 2] for k, v in batch.items()}
        else:
            half = {k: v[:, :S // 2] for k, v in batch.items()}
        return real(state, half)
    return step


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = run_small(cell)
    assert res["correct"] is True, res["checks"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [unchanged_step, half_batch_step],
                         ids=["state_unchanged", "half_batch"])
def test_broken_step_is_caught(cell, fault):
    res = run_small(cell, step_factory=fault)
    assert res["correct"] is False, res["checks"]
