"""A cell on a mesh of four chips, on four virtual CPU devices.

One subprocess (``--xla_force_host_platform_device_count=4``; the test
process itself sees one device) runs the small copy of the 2 x 2 cell
through ``run_cell`` and reports what the tests below check: the run is
correct; the state the window saves carries the ``Partitioner``'s
shardings, and its save restores bit for bit onto the mesh; the faults a
step can have come out not correct; the plain reference spread over the
four devices reads what it reads on one. The fullest device's peak and
``step_mfu`` over the mesh's chips are checked on hand-made inputs."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from _util import CHIP, ROOT, cell_files, spec

CELL = "stablelm-3b.save_paced_4chip"

SCRIPT = r"""
import json, sys, tempfile
sys.path.insert(0, sys.argv[1])
import jax, numpy as np
import _util
from chipbench import loop, reference
from test_chipbench_faults import half_batch_step, unchanged_step
from test_chipbench_altered import _nudge

CELL = sys.argv[2]
out = {"devices": len(jax.devices())}
bench, files = _util.small_files(CELL)
run_mod = _util.load_run_module()


def run(step_factory=None):
    with tempfile.TemporaryDirectory() as work:
        return run_mod.run_cell(bench, CELL, 20261019, 1.5, 0,
                                require_tpu=False, compile_cache=False,
                                step_factory=step_factory, files=files,
                                work_dir=work, log=lambda *a, **k: None)


def differ(tree, shardings):
    return [jax.tree_util.keystr(p) for (p, a), s in zip(
        jax.tree_util.tree_flatten_with_path(tree)[0],
        jax.tree_util.tree_leaves(shardings))
        if not a.sharding.is_equivalent_to(s, a.ndim)]


seen = {"saved": [], "restored": [], "spread": []}
real_save, real_restore = loop.Cell.save, loop.Cell.restore_fingerprint


def save(self, trainer, step, state):
    seen["saved"].append(differ(state, self.shardings))
    seen["spread"].append(min(len(a.sharding.device_set) for a in
                              jax.tree_util.tree_leaves(state["params"])))
    return real_save(self, trainer, step, state)


def restore_fingerprint(self, step):
    got = self.trainer.ckpt.restore(
        state_template=self.trainer._full_state(self.template),
        step=step)["train"]
    seen["restored"].append(differ(got, self.shardings))
    return real_restore(self, step)


loop.Cell.save, loop.Cell.restore_fingerprint = save, restore_fingerprint
out["sound"] = run()
out["seen"] = seen
loop.Cell.save, loop.Cell.restore_fingerprint = real_save, real_restore
out["state_unchanged"] = run(unchanged_step)
out["half_batch"] = run(half_batch_step)

from repro.core.checkpoint import CheckpointManager
real_ckpt_save = CheckpointManager.save


def altered_save(self, step, state, **kw):
    state = dict(state, train=_nudge(state["train"]))
    return real_ckpt_save(self, step, state, **kw)


CheckpointManager.save = altered_save
out["altered_save"] = run()
CheckpointManager.save = real_ckpt_save

# the reference over the four devices and on one, same weights and tokens
from repro.models.config import ModelConfig
from repro.train.steps import init_train_state
from chipbench import state as S
entry, config, traffic = files
m = dict(config["model"])
m["block_pattern"] = tuple(m.get("block_pattern") or ())
shape = jax.eval_shape(
    lambda: init_train_state(jax.random.key(0), ModelConfig(**m)))["params"]
t = config["train"]
batches = [S.batch_at(7, k, t["batch"], t["seq_len"], m["vocab_size"],
                      traffic["zipf_a"]) for k in (1, 2, 3)]
spread = reference.run_reference(config, 7, shape, batches,
                                 devices=jax.devices())
one = reference.run_reference(config, 7, shape, batches)
places = reference.placement(shape, jax.devices())
out["reference"] = {
    "losses": [spread.losses, one.losses],
    "grad_norms": [spread.grad_norms.tolist(), one.grad_norms.tolist()],
    "update_norms": [spread.update_norms.tolist(),
                     one.update_norms.tolist()],
    "split_leaves": sum(not s.is_fully_replicated
                        for s in jax.tree_util.tree_leaves(places))}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), CHIP]))
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT, os.path.dirname(__file__), CELL],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_cell_asks_for_the_mesh_it_runs_on(four):
    entry, config, _ = cell_files(CELL)
    assert entry["chips"] == 4 and config["mesh"] == {"data": 2, "model": 2}
    assert four["devices"] == 4
    assert four["sound"]["device"]["count"] == 4


def test_sound_run_on_the_mesh_is_correct(four):
    res = four["sound"]
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_saved_state_keeps_the_partitioners_layout(four):
    """Every save of the window gets the state in the Trainer's layout, its
    params on all four devices (split over model, whole on each data
    replica), not the layout the compiler would pick for the step's
    output."""
    seen = four["seen"]
    assert seen["saved"] and all(not d for d in seen["saved"]), seen["saved"]
    assert min(seen["spread"]) == 4


def test_window_save_restores_bit_for_bit_onto_the_mesh(four):
    checks = four["sound"]["checks"]
    assert checks["saves_missing"] == [0, 0]
    assert checks["last_save_lost"] == [0, 0]
    assert checks["restored_leaves_differ"] == [0, 0]
    assert four["seen"]["restored"] == [[]]


@pytest.mark.parametrize("fault,check", [
    ("state_unchanged", None), ("half_batch", None),
    ("altered_save", "restored_leaves_differ")])
def test_faults_on_the_mesh_are_caught(four, fault, check):
    res = four[fault]
    assert res["correct"] is False, res["checks"]
    if check:
        assert res["checks"][check][0] >= 1


def test_reference_spread_over_devices_reads_as_on_one(four):
    ref = four["reference"]
    assert ref["split_leaves"] > 0
    spread, one = ref["losses"]
    assert spread == pytest.approx(one, rel=1e-6)
    for key in ("grad_norms", "update_norms"):
        spread, one = ref[key]
        assert spread == pytest.approx(one, rel=1e-4, abs=1e-9), key


def test_peak_is_the_fullest_devices():
    from chipbench import loop
    devices = [NS(memory_stats=lambda n=n: {"peak_bytes_in_use": n})
               for n in (5, 9, 7)]
    assert loop.peak_bytes(devices) == 9
    assert loop.peak_bytes([NS(memory_stats=lambda: None)]) == 0


@pytest.mark.parametrize("chips", [1, 4])
def test_step_mfu_divides_by_the_meshs_chips(chips):
    """1e15 FLOPs a step of the global batch in 2 s of device time (each
    device's run of the step counts), on ``chips`` chips of 197 TFLOP/s."""
    reader = spec.metric_reader("step_mfu")
    trace = NS(module_s={"jit_train_step": [1.5, 2.5] * chips})
    run = NS(device_trace=trace, flops_per_step=1e15, chips=chips,
             peaks={"bf16_flops": 197e12})
    want = 100 * 1e15 / (2 * 197e12 * chips)
    assert reader.read(run) == pytest.approx(want)
