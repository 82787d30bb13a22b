"""Streaming restore pipeline: streaming/monolithic parity, out-of-order
extent arrival, in-stream CRC verification, backpressure, prefetcher-fed
streams, and abort cleanup (DESIGN.md §10)."""

import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CheckpointManager, ChecksumError, EngineConfig,
                        MultiLevelCheckpointer, make_cr_engine)
from repro.core import pipeline as pipeline_mod
from repro.core.aggregation import Strategy
from repro.core.buffers import PAGE
from repro.core.checkpoint import RestoreMetrics
from repro.core.engines import ReadReq, SaveItem
from repro.core.engines.aggregated import _AggReadStream
from repro.core.manifest import Manifest, crc32_of
from repro.core.pipeline import RestorePipeline, RestoreTask


def _state(scale=1):
    return {
        "params": {"w": jnp.arange(256 * 64 * scale,
                                   dtype=jnp.float32).reshape(256, -1),
                   "b": jnp.full((64,), 0.5, jnp.bfloat16)},
        "opt": {"mu": jax.random.normal(jax.random.key(3),
                                        (128, 512 * scale))},
        "data": {"cursor": np.arange(777, dtype=np.int64)},
        "step": 11,
    }


def _leaves(tree):
    flat, _ = jax.tree_util.tree_flatten(tree)
    return [np.asarray(x) for x in flat if hasattr(x, "shape")]


def _assert_tree_equal(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(x, y)


# -------------------------------------------------------------- mode parity
@pytest.mark.parametrize("quantize", [False, True])
def test_streaming_bit_identical_to_monolithic(quantize, tmp_path):
    """One checkpoint, restored by both modes: every leaf (incl. dequantized
    moments) must be bit-identical — streaming changes scheduling, not data."""
    state = _state(scale=2)
    qp = ("opt/mu",) if quantize else ()
    d = str(tmp_path / "ck")
    with CheckpointManager(d, quantize_prefixes=qp) as mgr:
        mgr.save(1, state)
    with CheckpointManager(d, quantize_prefixes=qp, streaming=True) as m_s:
        r_stream = m_s.restore(state_template=state)
        assert m_s.last_restore_metrics.mode == "streaming"
    with CheckpointManager(d, quantize_prefixes=qp, streaming=False) as m_m:
        r_mono = m_m.restore(state_template=state)
        assert m_m.last_restore_metrics.mode == "monolithic"
    _assert_tree_equal(r_stream, r_mono)
    np.testing.assert_array_equal(np.asarray(r_stream["params"]["w"]),
                                  np.asarray(state["params"]["w"]))


def test_streaming_metrics_overlap_accounting(tmp_path):
    state = _state(scale=4)
    d = str(tmp_path / "ck")
    with CheckpointManager(d, quantize_prefixes=("opt/mu",)) as mgr:
        mgr.save(1, state)
        mgr.restore(state_template=state)
        m = mgr.last_restore_metrics
    assert m.mode == "streaming"
    assert m.peak_staged_bytes > 0
    assert m.decode_seconds > 0          # quantized moments were unpacked
    # the read stage spans the whole stream, so it alone can approach e2e;
    # the consumer's stall must not exceed the stage span
    assert m.read_stall_seconds <= m.read_seconds + 1e-3
    assert m.stage_seconds >= m.read_seconds
    assert m.overlap_seconds >= 0.0
    assert m.end_to_end_seconds > 0


RESHARD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, sys
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import CheckpointManager
devs = jax.devices()
mesh_a = Mesh(np.array(devs).reshape(2, 4), ("data", "model"))
mesh_b = Mesh(np.array(devs).reshape(4, 2), ("data", "model"))
w = jnp.arange(64 * 128, dtype=jnp.float32).reshape(64, 128)
state = {"w": jax.device_put(w, NamedSharding(mesh_a, P("data", "model")))}
d = sys.argv[1]
tmpl = {"w": jax.ShapeDtypeStruct(w.shape, w.dtype,
        sharding=NamedSharding(mesh_b, P("model", "data")))}
with CheckpointManager(d, streaming=True) as mgr:
    mgr.save(1, state)
    r_s = mgr.restore(state_template=tmpl)
    assert mgr.last_restore_metrics.mode == "streaming"
with CheckpointManager(d, streaming=False) as mgr:
    r_m = mgr.restore(state_template=tmpl)
np.testing.assert_array_equal(np.asarray(r_s["w"]), np.asarray(w))
np.testing.assert_array_equal(np.asarray(r_s["w"]), np.asarray(r_m["w"]))
print("RESHARD-STREAM-OK")
"""


def test_streaming_resharded_restore_multidevice(tmp_path):
    """Save on a 2x4 mesh, restore on 4x2 through the streaming pipeline —
    windowed assembly fed by streamed pieces must match the monolithic
    full-lookup result bit for bit."""
    env = {**os.environ, "PYTHONPATH": "src"}
    p = subprocess.run([sys.executable, "-c", RESHARD, str(tmp_path / "d")],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       timeout=300)
    assert "RESHARD-STREAM-OK" in p.stdout, p.stderr[-2000:]


# -------------------------------------------------- stream-level behaviours
def _save_items(eng, d, sizes, rng, **kw):
    items = [SaveItem(f"t{i}", rng.integers(0, 256, (n,), np.uint8)
                      if n else np.zeros((0,), np.uint8),
                      "uint8", (n,), ((0, n),)) for i, n in enumerate(sizes)]
    m = eng.save(d, items, **kw)
    return items, m


def test_out_of_order_get(tmp_path, rng):
    """Consumers may get keys in any order (the stream exceeds its budget
    one unit at a time rather than deadlocking on landed results)."""
    eng = make_cr_engine("aggregated", EngineConfig(
        chunk_bytes=1 << 20, coalesce_bytes=1 << 20, inflight_bytes=2 << 20,
        strategy=Strategy.FILE_PER_PROCESS))
    d = str(tmp_path / "ooo")
    sizes = [1 << 20, 777, 3 << 20, 0, 65536, 1 << 20]   # incl. chunked + empty
    items, m = _save_items(eng, d, sizes, rng, step=1)
    reqs = [ReadReq(k, r.shards[0].path, r.shards[0].offset,
                    r.shards[0].nbytes) for k, r in m.tensors.items()]
    stream = eng.begin_restore(d, reqs)
    for it in reversed(items):          # reverse of layout order
        got = stream.get(it.key)
        assert got.tobytes() == bytes(memoryview(it.data)), it.key
    stream.end_restore()
    with pytest.raises(KeyError):
        stream2 = eng.begin_restore(d, reqs)
        stream2.get("t0")
        stream2.get("t0")               # double consumption
    stream2.abort()
    eng.close()


def test_restore_backpressure_caps_staged_bytes(tmp_path, rng):
    """In-order consumption keeps staged bytes (read buffers + landed
    results) within inflight_bytes; monolithic read of the same checkpoint
    peaks at full size."""
    budget = 2 << 20
    eng = make_cr_engine("aggregated", EngineConfig(
        chunk_bytes=1 << 20, coalesce_bytes=1 << 20, inflight_bytes=budget,
        strategy=Strategy.FILE_PER_PROCESS))
    d = str(tmp_path / "bp")
    sizes = [1 << 20] * 8 + [6 << 20]
    items, m = _save_items(eng, d, sizes, rng, step=1)
    reqs = [ReadReq(it.key, m.tensors[it.key].shards[0].path,
                    m.tensors[it.key].shards[0].offset,
                    m.tensors[it.key].shards[0].nbytes) for it in items]
    stream = eng.begin_restore(d, reqs)
    for it in items:                    # layout order
        stream.get(it.key)
    stats = stream.end_restore()
    assert 0 < stats.peak_staged_bytes <= budget
    assert stats.logical_bytes == sum(sizes)
    # every extent here stands alone, so every read lands straight in the
    # array get() returns: counted while in flight, the consumer's after
    assert stats.direct_bytes == sum(sizes)
    assert eng.pool.outstanding_bytes == 0
    eng.close()


def test_manager_restore_reports_bounded_staging(tmp_ckpt_dir):
    budget = 4 << 20
    cfg = EngineConfig(inflight_bytes=budget, chunk_bytes=1 << 20,
                       coalesce_bytes=1 << 20)
    state = _state(scale=8)             # ~several MB of tensors
    with CheckpointManager(tmp_ckpt_dir, config=cfg) as mgr:
        mgr.save(1, state)
        mgr.restore(state_template=state)
        assert 0 < mgr.last_restore_metrics.peak_staged_bytes <= budget
    with CheckpointManager(tmp_ckpt_dir, config=cfg, streaming=False) as mgr:
        mgr.restore(state_template=state)
        total = mgr.last_restore_metrics.total_bytes
        # monolithic stages every extent at once
        assert mgr.last_restore_metrics.peak_staged_bytes >= total // 2


# ------------------------------------------------------------ CRC verification
def _corrupt_extent(ckpt_root, step, key):
    man = Manifest.load(os.path.join(ckpt_root, f"step_{step:08d}"))
    sh = man.tensors[key].shards[0]
    path = os.path.join(ckpt_root, f"step_{step:08d}", sh.path)
    with open(path, "r+b") as f:
        f.seek(sh.offset + min(8, max(sh.nbytes - 4, 0)))
        f.write(b"\xde\xad\xbe\xef")
    return sh


def test_crc_mismatch_raises_checksum_error(tmp_ckpt_dir):
    state = _state()
    with CheckpointManager(tmp_ckpt_dir, verify_crc=True) as mgr:
        mgr.save(1, state)
        sh = _corrupt_extent(tmp_ckpt_dir, 1, "params/w")
        with pytest.raises(ChecksumError) as ei:
            mgr.restore(state_template=state)
        assert "params/w" in str(ei.value)      # names the key...
        assert str(sh.offset) in str(ei.value)  # ...and the offset


def test_crc_optout_restores_corrupt_bytes(tmp_ckpt_dir):
    """verify_crc=False (EngineConfig.checksum unset) skips verification —
    the corrupted bytes come back unchecked."""
    state = _state()
    with CheckpointManager(tmp_ckpt_dir, verify_crc=True) as mgr:
        mgr.save(1, state)
    _corrupt_extent(tmp_ckpt_dir, 1, "params/w")
    with CheckpointManager(tmp_ckpt_dir, verify_crc=False) as mgr:
        r = mgr.restore(state_template=state)   # no raise
    assert not np.array_equal(np.asarray(r["params"]["w"]),
                              np.asarray(state["params"]["w"]))


def test_crc_verified_in_buffered_fallback(tmp_ckpt_dir):
    """Engines without a native read stream still verify through the
    buffered fallback. datastates/snapshot record no CRCs, so drive the
    fallback through the base-class path on the aggregated format."""
    from repro.core.engines.base import CREngine
    state = _state()
    with CheckpointManager(tmp_ckpt_dir, verify_crc=True) as mgr:
        mgr.save(1, state)
        sh = _corrupt_extent(tmp_ckpt_dir, 1, "params/w")
        step_dir = os.path.join(tmp_ckpt_dir, "step_00000001")
        req = ReadReq("params/w@0", sh.path, sh.offset, sh.nbytes)
        # the base-class buffered fallback batches one read, verifies per get
        stream = CREngine.begin_restore(mgr.engine, step_dir, [req],
                                        crcs={req.key: sh.crc32})
        with pytest.raises(ChecksumError, match="params/w"):
            stream.get(req.key)
        stream.abort()


# -------------------------------------------------------------- abort cleanup
def test_restore_abort_releases_buffers_and_budget(tmp_ckpt_dir):
    """A mid-restore ChecksumError must settle the pooled-buffer and budget
    books: the SAME manager can save and restore again without wedging."""
    state = _state(scale=2)
    with CheckpointManager(tmp_ckpt_dir, verify_crc=True,
                           config=EngineConfig(inflight_bytes=2 << 20)
                           ) as mgr:
        mgr.save(1, state)
        _corrupt_extent(tmp_ckpt_dir, 1, "params/w")
        with pytest.raises(ChecksumError):
            mgr.restore(state_template=state, step=1)
        assert mgr.engine.pool.outstanding_bytes == 0   # books settled
        mgr.save(2, state)                              # no budget deadlock
        r = mgr.restore(state_template=state, step=2)
        np.testing.assert_array_equal(np.asarray(r["params"]["w"]),
                                      np.asarray(state["params"]["w"]))


# ------------------------------------------------------------ prefetcher-fed
def test_prefetcher_fed_stream_parity(tmp_path):
    """A level-1-only step prefetched into level 0 must stream to the same
    bytes as a local restore, promote the step, and attribute the pull."""
    state = _state()
    local, remote = str(tmp_path / "l"), str(tmp_path / "r")
    with MultiLevelCheckpointer(local, remote) as ml:
        ml.save(5, state)
        ml.wait()
        with CheckpointManager(local) as direct:
            r_local = direct.restore(state_template=state, step=5)
        shutil.rmtree(local)            # node loss: only level 1 remains
        os.makedirs(local)
        r = ml.restore(state_template=state)
        m = ml.last_restore_metrics
        assert m.mode == "streaming"
        assert m.prefetch_seconds > 0
        assert os.path.exists(os.path.join(local, "step_00000005",
                                           "manifest.json"))
    _assert_tree_equal(r, r_local)


def test_end_restore_drains_unconsumed_keys(tmp_path, rng):
    """Keys MAY be left unconsumed: end_restore must still drain (the final
    drain escapes the budget when landed results would otherwise wedge it)."""
    eng = make_cr_engine("aggregated", EngineConfig(
        chunk_bytes=1 << 20, coalesce_bytes=1 << 20, inflight_bytes=2 << 20,
        strategy=Strategy.FILE_PER_PROCESS))
    d = str(tmp_path / "uncons")
    sizes = [1 << 20] * 6          # 6 MB of requests vs a 2 MB budget
    items, m = _save_items(eng, d, sizes, rng, step=1)
    reqs = [ReadReq(it.key, m.tensors[it.key].shards[0].path,
                    m.tensors[it.key].shards[0].offset,
                    m.tensors[it.key].shards[0].nbytes) for it in items]
    stream = eng.begin_restore(d, reqs)
    assert stream.get("t0").tobytes() == bytes(memoryview(items[0].data))
    stream.end_restore()           # 5 unconsumed keys: must not spin
    assert eng.pool.outstanding_bytes == 0
    eng.close()


# ----------------------------------------------------- degenerate batch read
def test_batch_read_is_stream_client(tmp_path, rng):
    """engine.read() now drives the stream: same results, and small extents
    still coalesce to one I/O per group region."""
    eng = make_cr_engine("aggregated", EngineConfig(
        coalesce_bytes=64 << 20, strategy=Strategy.FILE_PER_PROCESS))
    d = str(tmp_path / "batch")
    sizes = [4096] * 16
    items, m = _save_items(eng, d, sizes, rng, step=1)
    reqs = [ReadReq(it.key, m.tensors[it.key].shards[0].path,
                    m.tensors[it.key].shards[0].offset,
                    m.tensors[it.key].shards[0].nbytes) for it in items]
    out = eng.read(d, reqs)
    for it in items:
        assert out[it.key].tobytes() == bytes(memoryview(it.data))
    assert eng.last_restore_stats.io_requests == 1   # one coalesced read
    eng.close()


def test_restore_abort_after_injected_engine_error(tmp_ckpt_dir):
    """A raw EIO (fault-injected at the pread syscall) mid-stream must take
    the same abort path as a CRC mismatch: budget units settled, pooled
    buffers returned, and the SAME manager saves and restores afterwards."""
    import errno

    from repro.core import faults

    state = _state(scale=2)
    with CheckpointManager(tmp_ckpt_dir, verify_crc=True,
                           config=EngineConfig(backend="threadpool",
                                               inflight_bytes=2 << 20)
                           ) as mgr:
        mgr.save(1, state)
        plan = faults.FaultPlan([faults.Fault(
            faults.OP_READ, at=2, action=faults.A_ERRNO, err=errno.EIO)])
        with faults.inject(plan):
            with pytest.raises(Exception) as ei:
                mgr.restore(state_template=state, step=1)
        assert plan.fired
        chain, e = [], ei.value
        while e is not None and e not in chain:
            chain.append(e)
            e = e.__cause__ or e.__context__
        assert any(isinstance(x, faults.InjectedIOError) for x in chain)
        assert mgr.engine.pool.outstanding_bytes == 0   # books settled
        mgr.save(2, state)                              # no budget deadlock
        r = mgr.restore(state_template=state, step=2)
        np.testing.assert_array_equal(np.asarray(r["params"]["w"]),
                                      np.asarray(state["params"]["w"]))


def test_restore_abort_after_injected_crash_mid_stream(tmp_ckpt_dir):
    """An InjectedCrash (worker death mid-pread) must also leave the engine
    reusable — the stream's abort path cannot depend on the error type."""
    from repro.core import faults

    state = _state(scale=2)
    with CheckpointManager(tmp_ckpt_dir, verify_crc=True,
                           config=EngineConfig(backend="threadpool",
                                               inflight_bytes=2 << 20)
                           ) as mgr:
        mgr.save(1, state)
        plan = faults.FaultPlan([faults.Fault(faults.OP_READ, at=1,
                                              action=faults.A_CRASH)])
        with faults.inject(plan):
            with pytest.raises(Exception):
                mgr.restore(state_template=state, step=1)
        assert plan.fired
        assert mgr.engine.pool.outstanding_bytes == 0
        r = mgr.restore(state_template=state, step=1)   # retry, clean run
        np.testing.assert_array_equal(np.asarray(r["params"]["w"]),
                                      np.asarray(state["params"]["w"]))


# ------------------------------------------------------------ direct landing
def _record_windows(monkeypatch):
    """The arrays the read stream hands out, and every WindowAssembler the
    restore pipeline builds (by tensor key)."""
    made, handed = {}, []

    class Recording(pipeline_mod.WindowAssembler):
        def __init__(self, record, wanted):
            super().__init__(record, wanted)
            made.setdefault(record.key, []).append(self)

    get = _AggReadStream.get

    def recording_get(stream, key):
        arr = get(stream, key)
        handed.append(arr)
        return arr

    monkeypatch.setattr(pipeline_mod, "WindowAssembler", Recording)
    monkeypatch.setattr(_AggReadStream, "get", recording_get)
    return made, handed


@pytest.mark.parametrize("align", [PAGE, 2 * PAGE])
@pytest.mark.parametrize("backend", ["posix", "threadpool"])
def test_direct_landing_matches_monolithic(backend, align, tmp_path,
                                           monkeypatch):
    """A tensor that fills its window is read straight into page-aligned
    memory that becomes the window array (a coalesced small tensor's copy
    out of the pooled buffer does too): the leaf is the array the stream
    handed out, and the bytes equal the monolithic restore's.
    Byte counts that are not a multiple of ``align`` pad the landing buffer,
    never the window."""
    chunk = 1 << 20
    n = 3 * chunk // 4 + 5              # 3 MiB + 20 B: four units, unaligned
    state = {"a": np.arange(2 * n, dtype=np.float32)[::-1].copy(),
             "b": np.full((7,), 0.5, np.float32),
             "c": np.arange(300, dtype=np.int32),
             "w": np.arange(n, dtype=np.float32)}
    cfg = EngineConfig(backend=backend, chunk_bytes=chunk,
                       coalesce_bytes=chunk, inflight_bytes=4 * chunk,
                       align=align)
    d = str(tmp_path / "ck")
    made, handed = _record_windows(monkeypatch)
    with CheckpointManager(d, config=cfg) as mgr:
        mgr.save(1, state)
        got = mgr.restore()
        m = mgr.last_restore_metrics
        # "b" and "c" share a coalesced read, copied out of a pooled buffer
        assert m.direct_bytes == state["a"].nbytes + state["w"].nbytes
        for k in state:                 # every window is what get() gave
            (asm,) = made[k]
            assert np.shares_memory(got[k], asm.out)
            assert any(np.shares_memory(asm.out, h) for h in handed)
        assert made["a"][0].out.ctypes.data % PAGE == 0
        assert made["w"][0].out.ctypes.data % PAGE == 0
        # out-of-order gets straight off the stream
        man = Manifest.load(os.path.join(d, "step_00000001"))
        reqs = [ReadReq(k, rec.shards[0].path, rec.shards[0].offset,
                        rec.shards[0].nbytes)
                for k, rec in man.tensors.items()]
        stream = mgr.engine.begin_restore(os.path.join(d, "step_00000001"),
                                          reqs)
        for r in reversed(reqs):
            assert stream.get(r.key).tobytes() == state[r.key].tobytes()
        assert stream.end_restore().direct_bytes == m.direct_bytes
        assert mgr.engine.pool.outstanding_bytes == 0
    with CheckpointManager(d, config=cfg, streaming=False) as mgr:
        mono = mgr.restore()
    _assert_tree_equal(got, mono)
    _assert_tree_equal(got, state)


def _save_split_record(d, full):
    """One record saved as two half shards, as two writers would."""
    eng = make_cr_engine("aggregated", EngineConfig(checksum=True))
    h = len(full) // 2
    items = [SaveItem(f"w#{i}", full[lo:hi], "float32", full.shape,
                      ((lo, hi),), record_key="w")
             for i, (lo, hi) in enumerate([(0, h), (h, len(full))])]
    man = eng.save(d, items)
    return eng, man.tensors["w"]


@pytest.mark.parametrize("case", ["shards_into_window", "shard_into_windows",
                                  "quantized", "delta"])
def test_direct_landing_declined_keeps_copy_path(case, tmp_path,
                                                 monkeypatch):
    """Where the bytes read are not the window one to one (several shards
    per window, a shard split over windows, int8 moments to dequantize,
    delta chunk references to join), the window is never an array the
    stream handed out: a copy or a decode makes it, and the bytes equal
    the monolithic restore's."""
    full = np.arange(1 << 18, dtype=np.float32)
    d = str(tmp_path / "ck")
    made, handed = _record_windows(monkeypatch)

    def assert_no_window_is_read_memory(n_windows):
        windows = [a.out for asms in made.values() for a in asms]
        assert len(windows) >= n_windows and handed
        for win in windows:
            assert not any(np.shares_memory(win, h) for h in handed)

    if case == "shards_into_window":
        eng, rec = _save_split_record(d, full)
        m = RestoreMetrics(step=1)
        task = RestoreTask("w", rec, [(((0, len(full)),), None)])
        out = RestorePipeline(eng).run(d, [task], metrics=m)
        np.testing.assert_array_equal(out["w"], full)
        assert_no_window_is_read_memory(1)
        eng.close()
        return
    state = {"w": full}
    kw = {}
    if case == "quantized":
        state = {"mu": np.random.default_rng(1).standard_normal(
            1 << 18).astype(np.float32)}
        kw = dict(quantize_prefixes=("mu",))
    elif case == "delta":
        kw = dict(delta=True, delta_chunk_bytes=64 << 10)
    window_fn = None
    if case == "shard_into_windows":
        h = len(full) // 2
        window_fn = lambda rec: [(((0, h),), None), (((h, len(full)),), None)]
    with CheckpointManager(d, **kw) as mgr:
        mgr.save(1, state)
        if case == "delta":
            state = {"w": full.copy()}
            state["w"][:7] = -1.0
            mgr.save(2, state)
        got = mgr.restore(window_fn=window_fn)
        assert mgr.last_restore_metrics.mode == "streaming"
        assert_no_window_is_read_memory(2 if window_fn else 1)
    kw.pop("delta", None)               # the restore reads chunk refs anyway
    with CheckpointManager(d, streaming=False, **kw) as mgr:
        mono = mgr.restore(window_fn=window_fn)
    _assert_tree_equal(got, mono)
    if case == "shard_into_windows":
        np.testing.assert_array_equal(got["w"], full[:len(full) // 2])
    elif case == "delta":
        _assert_tree_equal(got, state)


@pytest.mark.parametrize("backend", ["posix", "threadpool"])
def test_direct_landing_crc_catches_corrupt_chunk(backend, tmp_path, rng):
    """A flipped byte inside a chunk read straight into the array ``get``
    would return raises ChecksumError; the abort then settles the budget,
    leaves no pooled buffer outstanding, and the engine restores cleanly
    afterwards."""
    chunk = 1 << 20
    eng = make_cr_engine("aggregated", EngineConfig(
        backend=backend, checksum=True, chunk_bytes=chunk,
        coalesce_bytes=chunk, inflight_bytes=4 * chunk,
        strategy=Strategy.FILE_PER_PROCESS))
    d = str(tmp_path / "crc")
    items, m = _save_items(eng, d, [4 * chunk + 100, 5000], rng, step=1)
    sh = m.tensors["t0"].shards[0]
    with open(os.path.join(d, sh.path), "r+b") as f:
        f.seek(sh.offset + chunk + 12345)    # inside the second chunk
        b = f.read(1)
        f.seek(sh.offset + chunk + 12345)
        f.write(bytes([b[0] ^ 0xFF]))

    reqs = [ReadReq(k, rec.shards[0].path, rec.shards[0].offset,
                    rec.shards[0].nbytes) for k, rec in m.tensors.items()]
    crcs = {k: rec.shards[0].crc32 for k, rec in m.tensors.items()}
    stream = eng.begin_restore(d, reqs, crcs=crcs)
    with pytest.raises(ChecksumError, match="t0"):
        stream.get("t0")
    assert stream.stats.direct_bytes > chunk   # the bad chunk landed direct
    stream.abort()
    assert stream.budget.in_flight == 0
    assert eng.pool.outstanding_bytes == 0
    with open(os.path.join(d, sh.path), "r+b") as f:   # repair, read again
        f.seek(sh.offset)
        f.write(np.asarray(items[0].data).tobytes())
    stream = eng.begin_restore(d, reqs, crcs=crcs)
    for it in items:
        assert stream.get(it.key).tobytes() == bytes(memoryview(it.data))
    assert stream.end_restore().direct_bytes == 4 * chunk + 100 + 5000
    eng.close()
