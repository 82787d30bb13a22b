"""Runs whose answer is altered where it is produced come out not
correct: the state a save writes, or the state a resume restores, one bit
off in one leaf (small size, on the CPU)."""

import jax
import jax.numpy as jnp
import pytest

from _util import run_small


def _nudge(state):
    """One leaf one step off in its last bit."""
    leaves, tdef = jax.tree_util.tree_flatten(state)
    i = max(range(len(leaves)), key=lambda j: leaves[j].size)
    x = leaves[i]
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    leaves[i] = jax.lax.bitcast_convert_type(bits ^ jnp.uint32(1), x.dtype)
    return jax.tree_util.tree_unflatten(tdef, leaves)


@pytest.mark.parametrize("cell,check", [
    ("stablelm-3b-8l.save_paced", "restored_leaves_differ"),
    ("stablelm-3b-8l.resume", "restored_states_differ")])
def test_altered_save_is_caught(cell, check, monkeypatch):
    from repro.core.checkpoint import CheckpointManager
    real = CheckpointManager.save

    def save(self, step, state, **kw):
        state = dict(state, train=_nudge(state["train"]))
        return real(self, step, state, **kw)
    monkeypatch.setattr(CheckpointManager, "save", save)
    res = run_small(cell)
    assert res["correct"] is False
    assert res["checks"][check][0] >= 1


def test_altered_restore_is_caught(monkeypatch):
    from repro.train.trainer import Trainer
    real = Trainer.resume

    def resume(self, state):
        restored, start, attr = real(self, state)
        return _nudge(restored), start, attr
    monkeypatch.setattr(Trainer, "resume", resume)
    res = run_small("stablelm-3b-8l.resume")
    assert res["correct"] is False
    assert res["checks"]["restored_states_differ"][0] >= 1
