"""Paper Fig 3 + DESIGN.md §9: checkpoint/restore overhead in the loop, and
the save-path mode comparison (blocking vs legacy-async vs pipelined).

Part 1 (mode comparison, always run; the §9 acceptance experiment): saves a
multi-tensor state through the three manager modes and records the best-of-N
``blocking_seconds`` per mode into a repo-root ``BENCH_pipeline.json``. The
comparison is copy-bound — legacy async blocks for a full host copy of every
shard, the pipelined save returns after submission — so it is stable on a
noisy disk.

Part 2 (trainer sweep, skipped with ``--smoke``): trains a reduced model and
measures per-iteration time with each engine in the loop, plus restore time —
the end-to-end framing of the paper's motivating experiment.
"""

from __future__ import annotations

import os
import shutil
import sys
import time

import numpy as np

from benchmarks.common import Report, SCRATCH, fresh_dir, write_summary

MODES = [
    ("blocking", dict(async_save=False, streaming=True)),
    ("legacy-async", dict(async_save=True, streaming=False)),
    ("pipelined", dict(async_save=True, streaming=True)),
]


def _mode_state(n_tensors: int, mb_per_tensor: int):
    import jax.numpy as jnp
    rng = np.random.default_rng(7)
    elems = mb_per_tensor * (1 << 20) // 4
    return {"params": {
        f"w{i}": jnp.asarray(rng.standard_normal(elems).astype(np.float32))
        for i in range(n_tensors)}}


def run_mode_comparison(rep: Report, smoke: bool = False) -> dict:
    from repro.core import CheckpointManager

    n_tensors = 16
    mb = 2 if smoke else 6
    reps = 5
    state = _mode_state(n_tensors, mb)
    total = n_tensors * mb << 20

    out = {"state_bytes": total, "tensors": n_tensors, "reps": reps,
           "modes": {}}
    for name, kw in MODES:
        d = fresh_dir(f"mode_{name.replace('-', '_')}")
        best_block, best_e2e = float("inf"), float("inf")
        with CheckpointManager(d, keep=2, **kw) as mgr:
            mgr.save(0, state)     # warm: pool buffers, file prealloc, jit
            mgr.wait()
            for r in range(1, reps + 1):
                os.sync()          # writeback from the previous rep/mode
                m = mgr.save(r, state)
                mgr.wait()         # e2e is filled once the flush commits
                best_block = min(best_block, m.blocking_seconds)
                best_e2e = min(best_e2e, m.end_to_end_seconds)
        out["modes"][name] = {"blocking_seconds": round(best_block, 6),
                              "end_to_end_seconds": round(best_e2e, 6)}
        rep.add(config=f"mode-{name}", blocking_s=best_block,
                end_to_end_s=best_e2e, state_mb=total >> 20)

    legacy = out["modes"]["legacy-async"]["blocking_seconds"]
    piped = out["modes"]["pipelined"]["blocking_seconds"]
    out["pipelined_vs_legacy_blocking_speedup"] = round(
        legacy / piped if piped else float("inf"), 2)
    out["pipelined_wins"] = piped < legacy
    write_summary("pipeline", out)
    print(f"  -> BENCH_pipeline.json: pipelined {piped * 1e3:.2f} ms vs "
          f"legacy-async {legacy * 1e3:.2f} ms blocking "
          f"({out['pipelined_vs_legacy_blocking_speedup']}x)")
    return out


def run_trainer_sweep(rep: Report, quick: bool = False) -> None:
    from repro.configs import get_config
    from repro.core import CheckpointManager
    from repro.data import DataConfig
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_config("stablelm-3b").scaled_down(
        layers=2 if quick else 4, width_div=16 if quick else 8, vocab=2048)
    steps = 12 if quick else 30
    ckpt_every = 4 if quick else 10
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=128, global_batch=8)

    baseline_wall = None
    for engine, async_ in [(None, False), ("aggregated", True),
                           ("aggregated", False), ("datastates", False),
                           ("snapshot", False), ("torchsave", False)]:
        d = fresh_dir(f"train_{engine}_{async_}")
        tcfg = TrainerConfig(steps=steps,
                             ckpt_every=ckpt_every if engine else 0,
                             ckpt_dir=d, ckpt_engine=engine or "aggregated",
                             async_ckpt=async_, log_every=0)
        t = Trainer(cfg, tcfg, data_cfg=data)
        out = t.run()
        label = "no-ckpt" if engine is None else \
            f"{engine}{'-async' if async_ else ''}"
        wall = out["wall_seconds"]
        if engine is None:
            baseline_wall = wall
        n_ckpts = steps // ckpt_every if engine else 0
        over = (wall - baseline_wall) / n_ckpts if n_ckpts else 0.0
        restore_s = 0.0
        if engine:
            t0 = time.perf_counter()
            with CheckpointManager(d, engine=engine or "aggregated") as mgr:
                mgr.restore(state_template={
                    "train": out["state"],
                    "data": {"data_step": 0}})
            restore_s = time.perf_counter() - t0
        t.close()
        rep.add(config=label, wall_s=wall,
                per_ckpt_overhead_s=over,
                ckpt_blocking_s=out["ckpt_blocking_seconds"],
                ckpt_blocking_reported_s=out["ckpt_blocking_reported_s"],
                restore_s=restore_s)


def run(full_scale: bool = False, quick: bool = False, smoke: bool = False):
    rep = Report("bench_train_overhead")
    modes = run_mode_comparison(rep, smoke=smoke)
    if not smoke:
        run_trainer_sweep(rep, quick=quick)
    path = rep.save()
    if smoke and not modes["pipelined_wins"]:
        print("SMOKE FAIL: pipelined blocking_seconds not below legacy-async",
              file=sys.stderr)
        sys.exit(1)
    return path


if __name__ == "__main__":
    from benchmarks.common import trace_from_argv
    trace_from_argv()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run(full_scale="--full-scale" in sys.argv, quick="--quick" in sys.argv,
        smoke="--smoke" in sys.argv)
