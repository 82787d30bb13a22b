"""Restore-path benchmarks.

Part 1 (paper Figs 13–14): restore-pipeline breakdown — memory allocation vs
PFS reads — for DataStates-style dynamic allocation vs pooled (preallocated)
buffers. The paper's finding: excluding allocation nearly doubles restore
throughput; pooled buffers recover it.

Part 2 (DESIGN.md §10; always run, the only part under ``--smoke``):
monolithic vs streaming restore through the CheckpointManager. Each mode
restores the same checkpoint in a fresh process (cold page cache, best-of-N)
and reports end-to-end wall, peak host RSS, and the engine's peak staged
bytes. The gate: streaming must be no slower end-to-end, bound its staging
by ``inflight_bytes`` (monolithic stages the full checkpoint), and produce
bit-identical state. Results land in repo-root ``BENCH_restore.json``
(``make verify`` and CI run ``--smoke``).
"""

from __future__ import annotations

import multiprocessing as mp
import os
import queue
import sys
import time
import zlib

from benchmarks.common import (Report, drop_caches, fresh_dir,
                               synthetic_layout, write_summary)
from benchmarks.crbench import bench_read, bench_write



# ------------------------------------------------------- part 1: allocation
def run_alloc_breakdown(rep: Report, full_scale: bool, quick: bool) -> None:
    per_rank = (8 << 30) if full_scale else (512 << 20)
    ranks = 4
    if quick:
        per_rank = 128 << 20
        ranks = 2
    # smaller regions -> more allocations, the effect the paper profiles
    region = 16 << 20

    lay = synthetic_layout(ranks, per_rank, region_bytes=region)
    d = fresh_dir("alloc")
    bench_write(lay, "aggregated", {"strategy": "file_per_process"}, d)

    for engine, pooled, label in [
            ("datastates", False, "datastates (dynamic alloc)"),
            ("datastates", True, "datastates (+pool, paper's fix)"),
            ("aggregated", True, "aggregated (pooled)")]:
        cfg = {"strategy": "file_per_process", "pooled_buffers": pooled,
               "chunk_bytes": region}
        r = bench_read(lay, engine, cfg, d)
        alloc_frac = r["alloc_s"] / r["wall_s"] if r["wall_s"] else 0.0
        rep.add(config=label, read_gbps=r["gbps"],
                alloc_seconds=r["alloc_s"], copy_seconds=r["copy_s"],
                alloc_fraction=alloc_frac, read_reqs=r["io_requests"])


# -------------------------------------------- part 2: monolithic vs streaming
def _build_checkpoint(d: str, n_float: int, n_quant: int, mb: int,
                      inflight: int) -> int:
    import numpy as np
    import jax.numpy as jnp
    from repro.core import CheckpointManager, EngineConfig

    rng = np.random.default_rng(11)
    elems = mb * (1 << 20) // 4
    state = {
        "params": {f"w{i}": jnp.asarray(
            rng.standard_normal(elems).astype(np.float32))
            for i in range(n_float)},
        "opt": {"mu": {f"m{i}": jnp.asarray(
            rng.standard_normal(elems).astype(np.float32))
            for i in range(n_quant)}},
    }
    with CheckpointManager(d, quantize_prefixes=("opt/mu",),
                           config=EngineConfig(inflight_bytes=inflight)
                           ) as mgr:
        m = mgr.save(0, state)
    return m.total_bytes


def _restore_child(d: str, streaming: bool, inflight: int) -> dict:
    """Fresh-process restore: peak RSS is this run's, not the parent's."""
    import resource

    import jax
    import numpy as np
    from repro.core import CheckpointManager, EngineConfig

    t0 = time.perf_counter()
    with CheckpointManager(d, quantize_prefixes=("opt/mu",),
                           streaming=streaming,
                           config=EngineConfig(inflight_bytes=inflight)
                           ) as mgr:
        state = mgr.restore()          # host numpy via the saved lean tree
        wall = time.perf_counter() - t0
        m = mgr.last_restore_metrics
    digest = 0
    flat, _ = jax.tree_util.tree_flatten(state)
    for leaf in flat:
        if hasattr(leaf, "shape"):
            digest = zlib.crc32(np.ascontiguousarray(leaf), digest)
    return {"wall_s": wall, "digest": digest & 0xFFFFFFFF,
            "mode": m.mode,
            "read_s": m.read_seconds,
            "read_stall_s": m.read_stall_seconds,
            "decode_s": m.decode_seconds,
            "assemble_s": m.assemble_seconds,
            "stage_sum_s": m.stage_seconds,
            "overlap_s": m.overlap_seconds,
            "peak_staged_bytes": m.peak_staged_bytes,
            "peak_rss_bytes": resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss * 1024}


def _child_entry(q, fn, args) -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    q.put(fn(*args))


def _in_child(fn, *args):
    """Run ``fn(*args)`` in a fresh spawned process and return its result.

    Every step that touches JAX runs in such a child, one at a time, so
    this parent never holds an accelerator a child needs."""
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    p = ctx.Process(target=_child_entry, args=(q, fn, args))
    p.start()
    deadline = time.monotonic() + 1200
    out = None
    while out is None:
        try:
            out = q.get(timeout=2)
        except queue.Empty:
            if not p.is_alive():
                try:               # it may have put its result, then exited
                    out = q.get(timeout=1)
                    continue
                except queue.Empty:
                    pass           # crashed/OOM-killed: its stderr has why
                raise RuntimeError(
                    f"{fn.__name__} child died with exitcode {p.exitcode}")
            if time.monotonic() > deadline:
                p.kill()
                raise TimeoutError(f"{fn.__name__} child exceeded 1200s")
    p.join()
    return out


def run_mode_comparison(rep: Report, smoke: bool = False) -> dict:
    n_float, n_quant = (12, 6) if smoke else (24, 8)
    mb = 2 if smoke else 8
    inflight = (8 << 20) if smoke else (32 << 20)
    reps = 3

    d = fresh_dir("restore_modes")
    total = _in_child(_build_checkpoint, d, n_float, n_quant, mb, inflight)

    out = {"checkpoint_bytes": total, "inflight_bytes": inflight,
           "reps": reps, "modes": {}}
    for name, streaming in [("monolithic", False), ("streaming", True)]:
        best = None
        for _ in range(reps):
            os.sync()                  # writeback from the previous run
            drop_caches()              # cold reads: the restore we model
            r = _in_child(_restore_child, d, streaming, inflight)
            if best is None or r["wall_s"] < best["wall_s"]:
                best = r
        out["modes"][name] = {k: (round(v, 6) if isinstance(v, float) else v)
                              for k, v in best.items()}
        rep.add(config=f"restore-{name}", wall_s=best["wall_s"],
                read_stall_s=best["read_stall_s"],
                overlap_s=best["overlap_s"],
                peak_staged_mb=best["peak_staged_bytes"] >> 20,
                peak_rss_mb=best["peak_rss_bytes"] >> 20)

    mono, stream = out["modes"]["monolithic"], out["modes"]["streaming"]
    out["bit_identical"] = mono["digest"] == stream["digest"]
    out["streaming_wins_e2e"] = stream["wall_s"] <= mono["wall_s"]
    # gate with a 10% margin: without root, drop_caches() is a no-op and
    # warm-cache reads leave both modes within timing noise of each other
    out["gate_e2e_ok"] = stream["wall_s"] <= mono["wall_s"] * 1.10
    out["staging_bounded"] = (stream["peak_staged_bytes"] <= inflight
                              and mono["peak_staged_bytes"] >= total // 2)
    out["speedup_e2e"] = round(mono["wall_s"] / stream["wall_s"], 3) \
        if stream["wall_s"] else float("inf")
    write_summary("restore", out)
    print(f"  -> BENCH_restore.json: streaming {stream['wall_s'] * 1e3:.1f} "
          f"ms vs monolithic {mono['wall_s'] * 1e3:.1f} ms e2e "
          f"({out['speedup_e2e']}x); staged {stream['peak_staged_bytes'] >> 20}"
          f" MB (cap {inflight >> 20} MB) vs {mono['peak_staged_bytes'] >> 20}"
          f" MB; bit_identical={out['bit_identical']}")
    return out


def run(full_scale: bool = False, quick: bool = False, smoke: bool = False):
    rep = Report("bench_restore_alloc")
    if not smoke:
        run_alloc_breakdown(rep, full_scale, quick)
    modes = run_mode_comparison(rep, smoke=smoke)
    path = rep.save()
    if smoke:
        fails = [k for k in ("bit_identical", "gate_e2e_ok",
                             "staging_bounded") if not modes[k]]
        if fails:
            print(f"SMOKE FAIL: {', '.join(fails)}", file=sys.stderr)
            sys.exit(1)
    return path


if __name__ == "__main__":
    from benchmarks.common import trace_from_argv
    trace_from_argv()
    run(full_scale="--full-scale" in sys.argv, quick="--quick" in sys.argv,
        smoke="--smoke" in sys.argv)
