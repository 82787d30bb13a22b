import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves the distribution config is coherent at production
scale (sharding resolves, no unsupported collective, memory fits) and
extracts the roofline inputs:

    memory_analysis()  → per-device bytes (argument/temp/output)
    cost_analysis()    → per-device HLO FLOPs and bytes accessed
    compiled.as_text() → collective op volumes (all-gather / all-reduce /
                         reduce-scatter / all-to-all / collective-permute)

Usage:
    python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
    python -m repro.launch.dryrun --all --out results/dryrun.json
    python -m repro.launch.dryrun --all --multi-pod
"""

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import auto_axes, make_production_mesh
from repro.launch.specs import input_specs
from repro.models.config import SHAPES_BY_NAME
from repro.train.steps import make_prefill_step, make_serve_step, make_train_step

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Bytes of an HLO result type (handles tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def f32_cast_artifact_bytes(hlo_text: str, min_bytes: int = 32 << 20) -> int:
    """XLA:CPU lowers bf16 dots by converting operands to f32 — params and KV
    caches get duplicated in f32 (loop-invariant param converts are LICM-
    hoisted and live for the whole program; cache converts ride the while
    carry). TPU MXUs consume bf16 natively, so these buffers DO NOT exist on
    the target hardware. Counts each convert-producing op instance once
    (unique op name) above ``min_bytes`` so the roofline reports a
    TPU-adjusted peak alongside the raw CPU-lowered number."""
    total = 0
    seen: set[str] = set()
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.startswith("ROOT "):            # fusion bodies: counted via the
            continue                         # fusion instance line instead
        m = re.match(r"%(\S+) = f32\[([0-9,]+)\]\S*\s+(convert|fusion)\(", s)
        if not m:
            continue
        name, dims, op = m.groups()
        if op == "fusion" and "wrapped_convert" not in name:
            continue
        if name in seen:
            continue
        seen.add(name)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        if n * 4 >= min_bytes:
            total += n * 4
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result bytes of every collective op in post-SPMD HLO (per device)."""
    out = {c: 0 for c in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        lhs, rhs = s.split("=", 1)
        rhs = rhs.strip()
        for c in _COLLECTIVES:
            # match op invocation like: bf16[..] all-gather(...)
            if re.search(rf"\b{c}(-start|-done)?\(", rhs):
                ty = rhs.split(c)[0].strip()
                if c + "-done" in rhs:
                    continue  # volume was counted at -start
                out[c] += _shape_bytes(ty)
                out["count"] += 1
                break
    return out


def _microbatches(cfg, shape, mesh) -> int:
    """Gradient-accumulation depth: 1 sample per DP shard per microbatch,
    capped at 16 — keeps live activations ~(1, seq, d_model) per device."""
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp *= mesh.shape[a]
    return max(1, min(16, shape.global_batch // dp))


def _act_sharding(mesh, batch: int, seq_parallel: bool = False):
    """Residual-stream layout. ``seq_parallel=True`` additionally shards the
    sequence dim over 'model' (Megatron-style SP): GSPMD then lowers the
    per-layer TP all-reduces as reduce-scatter+all-gather — half the ICI
    traffic (the §Perf hillclimb move)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.sharding.partition import dp_axes
    import numpy as _np
    dp = dp_axes(mesh)
    dp_size = int(_np.prod([mesh.shape[a] for a in dp]))
    bdim = dp if batch % max(dp_size, 1) == 0 else None
    sdim = "model" if seq_parallel else None
    return NamedSharding(mesh, P(bdim, sdim, None))


def _jit_cell(cfg, shape, mesh, mode, specs, microbatches: int | None = None,
              seq_parallel: bool = False):
    """Build the jitted step + example ShapeDtypeStruct args for one cell."""
    if mode == "train":
        state_specs, batch_specs, shardings = specs
        mb = microbatches if microbatches is not None \
            else _microbatches(cfg, shape, mesh)
        act = _act_sharding(mesh, shape.global_batch // mb, seq_parallel)
        fn = jax.jit(make_train_step(cfg, microbatches=mb,
                                     grad_shardings=shardings["opt"]["mu"],
                                     act_sharding=act),
                     donate_argnums=(0,), out_shardings=(shardings, None))
        return fn, (state_specs, batch_specs)
    if mode == "prefill":
        param_specs, batch_specs, _ = specs
        fn = jax.jit(make_prefill_step(
            cfg, act_sharding=_act_sharding(mesh, shape.global_batch,
                                            seq_parallel)))
        return fn, (param_specs, batch_specs)
    param_specs, cache_specs, tok, pos, _, cache_sh = specs
    fn = jax.jit(make_serve_step(cfg), donate_argnums=(1,),
                 out_shardings=(None, cache_sh))
    return fn, (param_specs, cache_specs, tok, pos)


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             mesh_split: tuple[int, int] | None = None,
             microbatches: int | None = None,
             seq_parallel: bool = False) -> dict:
    """Lower + compile one cell. ``mesh_split=(dp, tp)`` overrides the
    default 16x16 single-pod split (hillclimb what-ifs)."""
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mesh_name = "2x16x16" if multi_pod else (
        f"{mesh_split[0]}x{mesh_split[1]}" if mesh_split else "16x16")
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "mode": shape.kind, "status": "ok",
           "microbatches": microbatches, "seq_parallel": seq_parallel}
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        rec["status"] = "skipped"
        rec["reason"] = ("pure full-attention arch: long_500k requires "
                         "sub-quadratic attention (DESIGN.md §6)")
        return rec
    try:
        t0 = time.perf_counter()
        if mesh_split is not None:
            mesh = jax.make_mesh(mesh_split, ("data", "model"),
                                 axis_types=auto_axes(2))
        else:
            mesh = make_production_mesh(multi_pod=multi_pod)
        spec_info = input_specs(cfg, shape, mesh)
        fn, args = _jit_cell(cfg, shape, mesh, spec_info["mode"],
                             spec_info["specs"], microbatches=microbatches,
                             seq_parallel=seq_parallel)
        with mesh:
            lowered = fn.lower(*args)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower
        mem = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        casts = f32_cast_artifact_bytes(hlo)
        n_dev = mesh.devices.size
        peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        rec.update({
            "devices": n_dev,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "per_device": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_hbm_bytes": peak,
                "cpu_cast_artifact_bytes": casts,
                # TPU-adjusted: casts don't exist on MXU hardware, but live
                # args+outputs (params, caches) are a hard floor
                "tpu_adjusted_peak_bytes": max(
                    peak - casts,
                    mem.argument_size_in_bytes + mem.output_size_in_bytes
                    - mem.alias_size_in_bytes),
                "flops": ca.get("flops", 0.0),
                "bytes_accessed": ca.get("bytes accessed", 0.0),
                "collective_bytes": coll,
            },
            "model": {
                "params": cfg.param_count(),
                "active_params": cfg.active_param_count(),
            },
        })
    except Exception as e:  # a failure here is a bug in the system
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES_BY_NAME))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES_BY_NAME:
                cells.append((arch, shape))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    results = []
    for arch, shape in cells:
        for mp in meshes:
            rec = run_cell(arch, shape, multi_pod=mp)
            results.append(rec)
            pd = rec.get("per_device", {})
            peak = pd.get("peak_hbm_bytes", 0) / 1e9
            print(f"[{rec['status']:7s}] {arch:22s} {shape:12s} "
                  f"{rec['mesh']:8s} peak={peak:6.2f}GB "
                  f"flops={pd.get('flops', 0):.3e} "
                  f"coll={sum(v for k, v in pd.get('collective_bytes', {}).items() if k != 'count') / 1e6:9.1f}MB"
                  + (f"  !! {rec.get('error', '')[:120]}"
                     if rec["status"] == "error" else ""),
                  flush=True)
            if args.out:
                os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                            exist_ok=True)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    bad = [r for r in results if r["status"] == "error"]
    print(f"\n{len(results) - len(bad)}/{len(results)} cells ok, "
          f"{len(bad)} errors")
    raise SystemExit(1 if bad else 0)


if __name__ == "__main__":
    main()
