"""Resharding planner properties + multi-device elastic restore."""

import os
import subprocess
import sys

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                     # container without hypothesis
    from _hypothesis_compat import given, settings, strategies as st

from repro.core.manifest import ShardEntry, TensorRecord
from repro.core.resharding import (assemble, dedupe_shards, intersect,
                                   normalize_index, plan_window)


def _grid_record(shape, splits):
    """Shard a tensor on an even grid; payload = offsets into arange."""
    rec = TensorRecord("t", "float32", shape)
    data = np.arange(np.prod(shape), dtype=np.float32).reshape(shape)
    extents = {}
    steps = [s // k for s, k in zip(shape, splits)]
    idx = [0] * len(shape)

    def rec_dims(d, window):
        if d == len(shape):
            window = tuple(window)
            sub = data[tuple(slice(lo, hi) for lo, hi in window)]
            path = f"data/{len(extents)}.bin"
            rec.shards.append(ShardEntry(window, path, 0, sub.nbytes))
            extents[(path, 0)] = np.ascontiguousarray(sub).view(np.uint8).reshape(-1)
            return
        for i in range(splits[d]):
            rec_dims(d + 1, window + [(i * steps[d], (i + 1) * steps[d])])

    rec_dims(0, [])
    return rec, data, extents


@settings(max_examples=25, deadline=None)
@given(splits=st.tuples(st.sampled_from([1, 2, 4]), st.sampled_from([1, 2, 4])),
       wsplits=st.tuples(st.sampled_from([1, 2, 4]), st.sampled_from([1, 2, 4])))
def test_any_regrid_assembles_exactly(splits, wsplits):
    """Property: saving on grid A and reading on grid B reproduces the tensor."""
    shape = (16, 32)
    rec, data, extents = _grid_record(shape, splits)
    lookup = lambda sh: extents[(sh.path, sh.offset)]
    steps = [s // k for s, k in zip(shape, wsplits)]
    for i in range(wsplits[0]):
        for j in range(wsplits[1]):
            window = ((i * steps[0], (i + 1) * steps[0]),
                      (j * steps[1], (j + 1) * steps[1]))
            out = assemble(rec, window, lookup)
            np.testing.assert_array_equal(
                out, data[window[0][0]:window[0][1],
                          window[1][0]:window[1][1]])


@pytest.mark.parametrize("writable", [True, False])
def test_exact_shard_becomes_the_window(writable):
    """A shard whose index is the window becomes the window with no copy
    when its bytes are writable; read-only bytes are copied, so the window
    stays writable."""
    rec, data, extents = _grid_record((16, 32), (2, 1))
    window = ((0, 8), (0, 32))
    (sh,) = [s for s in rec.shards if tuple(s.index) == window]
    raw = extents[(sh.path, sh.offset)]
    if not writable:
        raw = np.frombuffer(raw.tobytes(), np.uint8)
    out = assemble(rec, window, lambda s: raw)
    np.testing.assert_array_equal(out, data[:8])
    assert np.shares_memory(out, raw) == writable
    assert out.flags.writeable


def test_intersect():
    assert intersect(((0, 4),), ((2, 8),)) == ((2, 4),)
    assert intersect(((0, 4),), ((4, 8),)) is None
    assert intersect(((0, 4), (0, 2)), ((1, 2), (0, 2))) == ((1, 2), (0, 2))


def test_normalize_index():
    assert normalize_index((slice(2, 5),), (10,)) == ((2, 5),)
    assert normalize_index((slice(None),), (10,)) == ((0, 10),)
    assert normalize_index(None, (3, 4)) == ((0, 3), (0, 4))


def test_plan_window_incomplete_coverage_raises():
    rec = TensorRecord("t", "float32", (8,))
    rec.shards.append(ShardEntry(((0, 4),), "a", 0, 16))
    with pytest.raises(ValueError):
        plan_window(rec, ((0, 8),))


def test_dedupe_replicas():
    rec = TensorRecord("t", "float32", (4,))
    rec.shards.append(ShardEntry(((0, 4),), "a", 0, 16))
    rec.shards.append(ShardEntry(((0, 4),), "b", 0, 16))
    assert len(dedupe_shards(rec)) == 1


ELASTIC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, shutil, sys
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import CheckpointManager
devs = jax.devices()
mesh_a = Mesh(np.array(devs).reshape(2, 4), ("data", "model"))
mesh_b = Mesh(np.array(devs).reshape(4, 2), ("data", "model"))
w = jnp.arange(64 * 128, dtype=jnp.float32).reshape(64, 128)
state = {"w": jax.device_put(w, NamedSharding(mesh_a, P("data", "model")))}
d = sys.argv[1]
with CheckpointManager(d) as mgr:
    mgr.save(1, state)
    tmpl = {"w": jax.ShapeDtypeStruct(w.shape, w.dtype,
            sharding=NamedSharding(mesh_b, P("model", "data")))}
    r = mgr.restore(state_template=tmpl)
    np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(w))
print("ELASTIC-OK")
"""


def test_elastic_restore_multidevice(tmp_path):
    """Save under a 2x4 mesh, restore under 4x2 — in a fresh process with
    8 host devices (tests must not pollute this process's jax)."""
    env = {**os.environ, "PYTHONPATH": "src"}
    p = subprocess.run([sys.executable, "-c", ELASTIC, str(tmp_path / "d")],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       timeout=300)
    assert "ELASTIC-OK" in p.stdout, p.stderr[-2000:]
