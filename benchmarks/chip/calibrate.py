#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3

For each seed, in one process and at the cell's own sizes: the program's
first three steps (as every run takes them at set-up), the plain reference
in float32, the control (the reference with every matrix product's
operands rounded to float8 e4m3, one precision below the configuration's
bfloat16) and the fault "half of the batch left out" planted in the
reference put in the program's place. It prints, per seed, each one's
gaps against the float32 reference and its verdict at the cell's own
limits (the configuration's ``limits``, through the same ``Checks`` that
decides a run's ``correct``). It exits 1 if the program comes out not
correct, or the control or the fault comes out correct, on any seed. The
fault "a step that returns its state unchanged" reads 1 on both median
gaps by their definition and needs no run. The benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench import check, reference, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    import jax
    import numpy as np

    from chipbench import loop
    import run as bench_run

    bench = spec.load_benchmark()
    entry, config, traffic = spec.load_cell(bench, args.workload)
    devices = jax.devices()
    bench_run.require_chips(devices, entry["chips"])
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seeds = [int(s) for s in args.seeds.split(",")]
    ckpt_dir = os.path.join(bench_run.CKPT_ROOT, "calibrate")
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    os.makedirs(ckpt_dir)
    run = loop.Run(cell=args.workload, config=config, traffic=traffic,
                   seed=seeds[0], seconds=0, chips=entry["chips"])
    cell = loop.Cell(run, ckpt_dir)
    ok = True
    try:
        for seed in seeds:
            t0 = time.perf_counter()
            cell.reseed(seed)
            state, losses, gn = cell.first_steps(
                cell.trainer, cell.init_fn(cell.key), 3)
            upd = cell.upd_fn(cell.key, state["params"])
            prog = loop.program_readings(np.asarray(jax.device_get(losses)),
                                         np.asarray(gn), np.asarray(upd))
            del state
            shape = cell.state_shape["params"]
            batches = cell.host_batches(3)
            on = {"devices": cell.devices}
            ref = reference.run_reference(config, seed, shape, batches, **on)
            out = {"seed": seed, "losses": prog.losses,
                   "ref_losses": ref.losses,
                   "program": _gaps(prog, ref)}
            ctl = reference.run_reference(config, seed, shape, batches,
                                          precision="fp8", **on)
            half = reference.run_reference(config, seed, shape, batches,
                                           half_batch=True, **on)
            out["control"] = _gaps(ctl, ref)
            out["half_batch"] = _gaps(half, ref)
            out["seconds"] = time.perf_counter() - t0
            verdicts = judge(config["limits"], out)
            out["correct"] = verdicts
            ok = ok and verdicts == WANT
            print(json.dumps(out), flush=True)
    finally:
        cell.close()
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    print(json.dumps({"seeds": len(seeds), "as_expected": ok}), flush=True)
    return 0 if ok else 1


# what each reading has to come out as at the cell's limits
WANT = {"program": True, "control": False, "half_batch": False}


def judge(limits: dict, out: dict) -> dict:
    """``correct`` of each reading of one seed at the cell's ``limits``."""
    return {k: check.limit_checks(out[k], limits).correct for k in WANT}


def _gaps(a, ref) -> dict:
    g = check.training_gaps(a, ref)
    out = {k: g[k] for k in check.NUMBERS}
    out["worst_leaves"] = [g["grad_worst"], g["update_worst"]]
    return out


if __name__ == "__main__":
    sys.exit(main())
