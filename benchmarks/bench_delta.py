"""Content-addressed delta checkpointing sweep (DESIGN.md §12).

Sweeps dirty fraction ∈ {100%, 50%, 10%, 1%} × layout ∈ {file-per-tensor,
file-per-rank, single-file} through a ``delta=True`` CheckpointManager: step
0 is the full save (every chunk dirty by construction), then each following
step mutates a contiguous ``frac`` of every tensor's rows and saves again.
Recorded per cell: logical bytes actually written (``SaveMetrics.
written_bytes``), the written fraction vs the full save, end-to-end save
seconds, and the worker-side fingerprint/diff seconds plus D2H traffic —
the paper's *volume* axis should scale with the dirty fraction while
restore stays bit-identical. A ``baseline_blake2b`` cell re-runs the
1%-dirty single-file point with ``device_fingerprint=False`` so the json
carries the fp128-vs-blake2b speedup in one file (DESIGN.md §14).

``--smoke`` shrinks the state and gates on the §12/§14 acceptance criteria:
  · the 1%-dirty single-file save writes ≤ 10% of the full save's bytes,
  · the streaming restore of the delta step is bit-identical to a full
    (non-delta) save's restore of the same state,
  · after retention drops old steps, the refcount GC reaps unreferenced
    packs but every kept step still restores bit-exactly,
  · fp128 and blake2b produce the SAME dirty set (chunk counts + written
    bytes) over the same mutation schedule, with bit-identical restores,
  · on a device-held (jax) state, ``d2h_bytes`` never exceeds the dirty
    bytes plus the 16 B/chunk digest-table overhead — clean bytes stay
    on device.
Exits nonzero on any violation — wired into ``make verify`` and CI.
"""

from __future__ import annotations

import os
import shutil
import sys

import numpy as np

from benchmarks.common import Report, fresh_dir, write_summary

FRACTIONS = (1.0, 0.5, 0.1, 0.01)
LAYOUTS = [
    ("file-per-tensor", "file_per_tensor"),
    ("file-per-rank", "file_per_process"),
    ("single-file", "single_file"),
]


def _state(n_tensors: int, rows: int, cols: int) -> dict:
    rng = np.random.default_rng(12)
    return {"params": {
        f"w{i}": rng.standard_normal((rows, cols)).astype(np.float32)
        for i in range(n_tensors)}, "step": 0}


def _total_bytes(state) -> int:
    return sum(a.nbytes for a in state["params"].values())


def _mutate(state, frac: float, rep: int) -> None:
    """Touch a contiguous ``frac`` of every tensor's rows, offset per rep so
    consecutive saves dirty different chunks."""
    for a in state["params"].values():
        rows = a.shape[0]
        n = max(1, int(rows * frac))
        off = (rep * 7919) % max(rows - n, 1)
        a[off:off + n] += 1.0
    state["step"] = rep


def run_sweep(rep_log: Report, smoke: bool) -> dict:
    from repro.core import CheckpointManager, EngineConfig

    # tensors must dwarf the chunk grid for the 1% cell to be meaningful:
    # a 1% contiguous span can dirty at most span//chunk + 2 chunks
    n_tensors = 4
    rows = 2048 if smoke else 6144
    cols = 1024
    reps = 2 if smoke else 3
    out = {"chunk_bytes": 256 << 10, "reps": reps, "cells": {}}

    for label, strategy in LAYOUTS:
        for frac in FRACTIONS:
            state = _state(n_tensors, rows, cols)
            total = _total_bytes(state)
            out["state_bytes"] = total
            d = fresh_dir(f"delta_{strategy}_{int(frac * 100)}")
            cfg = EngineConfig(strategy=strategy)
            with CheckpointManager(d, config=cfg, delta=True,
                                   keep=None) as mgr:
                full = mgr.save(0, state)
                best_written, best_s, best_hash = float("inf"), \
                    float("inf"), float("inf")
                best_fp, best_diff, d2h = float("inf"), float("inf"), 0
                for r in range(1, reps + 1):
                    _mutate(state, frac, r)
                    os.sync()
                    m = mgr.save(r, state)
                    best_written = min(best_written, m.written_bytes)
                    best_s = min(best_s, m.end_to_end_seconds)
                    best_hash = min(best_hash, m.hash_seconds)
                    best_fp = min(best_fp, m.fingerprint_seconds)
                    best_diff = min(best_diff, m.diff_seconds)
                    d2h = max(d2h, m.d2h_bytes)
            wf = best_written / full.written_bytes
            out["cells"][f"{int(frac * 100)}%x{label}"] = {
                "dirty_fraction": frac, "layout": label,
                "full_written_bytes": full.written_bytes,
                "written_bytes": best_written,
                "written_fraction": round(wf, 4),
                "save_seconds": round(best_s, 6),
                "hash_seconds": round(best_hash, 6),
                "fingerprint_seconds": round(best_fp, 6),
                "diff_seconds": round(best_diff, 6),
                "d2h_bytes": d2h}
            rep_log.add(config=f"{int(frac * 100)}%-{label}",
                        written_mb=best_written / 1e6, written_frac=wf,
                        save_s=best_s, hash_s=best_hash,
                        fp_s=best_fp, diff_s=best_diff,
                        state_mb=total >> 20)

    # blake2b baseline at the acceptance point (1% dirty, single-file):
    # same schedule with device_fingerprint=False, so one json carries the
    # digest-engine speedup
    state = _state(n_tensors, rows, cols)
    d = fresh_dir("delta_blake2b_baseline")
    with CheckpointManager(d, config=EngineConfig(strategy="single_file"),
                           delta=True, keep=None,
                           device_fingerprint=False) as mgr:
        mgr.save(0, state)
        base_hash, base_s = float("inf"), float("inf")
        for r in range(1, reps + 1):
            _mutate(state, 0.01, r)
            m = mgr.save(r, state)
            base_hash = min(base_hash, m.hash_seconds)
            base_s = min(base_s, m.end_to_end_seconds)
    fp_cell = out["cells"]["1%xsingle-file"]
    speedup = base_hash / max(fp_cell["hash_seconds"], 1e-9)
    out["baseline_blake2b"] = {
        "dirty_fraction": 0.01, "layout": "single-file",
        "hash_seconds": round(base_hash, 6),
        "save_seconds": round(base_s, 6)}
    out["fingerprint_speedup"] = round(speedup, 2)
    rep_log.add(config="1%-single-file-blake2b", hash_s=base_hash,
                save_s=base_s, speedup=speedup)
    write_summary("delta", out)
    print(f"  -> BENCH_delta.json: {len(out['cells'])} cells, "
          f"{out['state_bytes'] >> 20} MB state, fp128 hash+diff "
          f"{speedup:.1f}x faster than blake2b")
    return out


def check_gates(smoke: bool) -> list[str]:
    """The §12 acceptance experiment (always run; sized small)."""
    from repro.core import CheckpointManager, EngineConfig

    errors: list[str] = []
    state = _state(4, 2048, 1024)          # 32 MB, 128 chunks of 256 KiB
    # fresh_dir purges the whole scratch: one call, then a sibling dir
    d = fresh_dir("delta_gate")
    d_full = os.path.join(os.path.dirname(d), "delta_gate_full")
    os.makedirs(d_full, exist_ok=True)

    cfg = EngineConfig(strategy="single_file")
    with CheckpointManager(d, config=cfg, delta=True, keep=2) as mgr:
        mgr.delta_gc_grace_s = 0.0
        full = mgr.save(0, state)
        _mutate(state, 0.01, 1)
        m1 = mgr.save(1, state)
        ratio = m1.written_bytes / full.written_bytes
        if ratio > 0.10:
            errors.append(f"1%-dirty save wrote {ratio:.1%} of full bytes "
                          f"(gate: <=10%)")
        # bit-identity: delta-step restore == full-save restore of same state
        with CheckpointManager(d_full, config=EngineConfig(
                strategy="single_file")) as ref:
            ref.save(1, state)
            want = ref.restore(step=1)
        got = mgr.restore(step=1)
        for k in state["params"]:
            if not np.array_equal(got["params"][k], want["params"][k]):
                errors.append(f"delta restore of {k} differs from "
                              f"full-save restore")
        # retention GC: roll old steps out; kept steps must stay restorable
        for r in range(2, 5):
            _mutate(state, 0.01, r)
            mgr.save(r, state)
        kept = mgr.all_steps()
        if kept != [3, 4]:
            errors.append(f"keep=2 retained {kept}")
        gc = mgr.last_gc_stats
        if gc is None or gc.kept == 0:
            errors.append("refcount GC never ran or pinned nothing")
        try:
            out = mgr.restore(step=kept[-1])
            for k, v in state["params"].items():
                if not np.array_equal(out["params"][k], v):
                    errors.append(f"post-GC restore of {k} not bit-identical")
        except Exception as e:  # noqa: BLE001 - gate must report, not die
            errors.append(f"post-GC restore failed: {e!r}")
    shutil.rmtree(d, ignore_errors=True)
    shutil.rmtree(d_full, ignore_errors=True)
    errors += _check_fingerprint_gates()
    return errors


def _check_fingerprint_gates() -> list[str]:
    """§14 gates: fp128 dirty-set parity with blake2b, and D2H avoidance
    on a device-held state (clean bytes never cross)."""
    import jax.numpy as jnp

    from repro.core import CheckpointManager, EngineConfig

    errors: list[str] = []
    state_fp = _state(4, 2048, 1024)       # 32 MB, 128 chunks of 256 KiB
    d_fp = fresh_dir("delta_gate_fp128")
    d_bl = os.path.join(os.path.dirname(d_fp), "delta_gate_blake2b")
    os.makedirs(d_bl, exist_ok=True)
    cfg = dict(config=EngineConfig(strategy="single_file"), delta=True,
               keep=None)

    # 1. dirty-set parity: identical mutation schedule through both digest
    #    engines must mark the same chunks dirty and restore bit-identically
    state_bl = _state(4, 2048, 1024)
    with CheckpointManager(d_fp, **cfg) as m_fp, \
            CheckpointManager(d_bl, device_fingerprint=False,
                              **cfg) as m_bl:
        for r in range(3):
            if r:
                _mutate(state_fp, 0.01, r)
                _mutate(state_bl, 0.01, r)
            a = m_fp.save(r, state_fp)
            b = m_bl.save(r, state_bl)
            if (a.chunks_total, a.chunks_dirty) != (b.chunks_total,
                                                    b.chunks_dirty):
                errors.append(
                    f"dirty-set parity: step {r} fp128 marked "
                    f"{a.chunks_dirty}/{a.chunks_total} dirty, blake2b "
                    f"{b.chunks_dirty}/{b.chunks_total}")
            if a.written_bytes != b.written_bytes:
                errors.append(f"dirty-set parity: step {r} wrote "
                              f"{a.written_bytes} (fp128) vs "
                              f"{b.written_bytes} (blake2b) bytes")
        got = m_fp.restore(step=2)
        want = m_bl.restore(step=2)
        for k in state_fp["params"]:
            if not np.array_equal(got["params"][k], want["params"][k]):
                errors.append(f"fp128 restore of {k} differs from blake2b")

    # 2. D2H avoidance: device-held state; traffic = digest tables
    #    (16 B/chunk) + dirty gathers only, never the clean bytes
    d_dev = os.path.join(os.path.dirname(d_fp), "delta_gate_device")
    os.makedirs(d_dev, exist_ok=True)
    dev = {"params": {k: jnp.asarray(v)
                      for k, v in _state(4, 2048, 1024)["params"].items()},
           "step": 0}
    with CheckpointManager(d_dev, **cfg) as mgr:
        m0 = mgr.save(0, dev)
        if m0.d2h_bytes <= 0:
            errors.append("device-state save reported zero d2h_bytes")
        host = {"params": {k: np.asarray(v).copy()
                           for k, v in dev["params"].items()}, "step": 0}
        _mutate(host, 0.01, 1)
        dev = {"params": {k: jnp.asarray(v)
                          for k, v in host["params"].items()}, "step": 1}
        m1 = mgr.save(1, dev)
        budget = m1.written_bytes + 16 * m1.chunks_total + (64 << 10)
        if m1.d2h_bytes > budget:
            errors.append(
                f"D2H gate: {m1.d2h_bytes} bytes crossed for a 1%-dirty "
                f"device save (budget {budget} = written + digest tables)")
        got = mgr.restore(step=1)
        for k, v in host["params"].items():
            if not np.array_equal(got["params"][k], v):
                errors.append(f"device-state delta restore of {k} not "
                              f"bit-identical")
    for p in (d_fp, d_bl, d_dev):
        shutil.rmtree(p, ignore_errors=True)
    return errors


def run(smoke: bool = False):
    rep = Report("bench_delta")
    run_sweep(rep, smoke=smoke)
    errors = check_gates(smoke)
    path = rep.save()
    for e in errors:
        print(f"SMOKE FAIL: {e}", file=sys.stderr)
    if errors:
        sys.exit(1)
    print("  delta gates: 1%-dirty <=10% bytes, bit-identical restore, "
          "refcount GC keeps every referenced chunk, fp128==blake2b dirty "
          "set, d2h <= dirty bytes + digest tables")
    return path


if __name__ == "__main__":
    from benchmarks.common import trace_from_argv
    trace_from_argv()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run(smoke="--smoke" in sys.argv)
