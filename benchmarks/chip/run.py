#!/usr/bin/env python3
"""Chip benchmark of training under checkpointing, and of resume.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the TPU this process finds: makes
the state and tokens from the seed, warms up, measures for ``--seconds``,
checks what the window produced against the plain reference and the bytes
it saved, and prints one JSON line last on standard output. ``--trace 0``
reports the cell's end-to-end metrics; ``--trace 1`` traces the window and
reports its per-layer metrics with the device's busy time and a breakdown.
With no TPU, or one not in the peaks table, it exits non-zero and prints
no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import mmap  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import spec  # noqa: E402
from chipbench.peaks import UnknownDevice  # noqa: E402

CKPT_ROOT = os.path.join(HERE, ".ckpt")


class NoChip(RuntimeError):
    pass


def o_direct_holds(directory: str) -> bool:
    """Whether an aligned O_DIRECT write succeeds in ``directory``."""
    path = os.path.join(directory, ".o_direct_probe")
    buf = mmap.mmap(-1, mmap.PAGESIZE)
    try:
        fd = os.open(path, os.O_CREAT | os.O_WRONLY | os.O_DIRECT, 0o644)
    except OSError:
        buf.close()
        return False
    try:
        return os.write(fd, buf) == mmap.PAGESIZE
    except OSError:
        return False
    finally:
        os.close(fd)
        os.unlink(path)
        buf.close()


def require_chips(devices, chips: int) -> dict:
    from chipbench.peaks import peaks_for  # raises UnknownDevice
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{len(devices)}")
    return peaks_for(devices[0].device_kind)


def traced_window(trace_dir: str):
    """Context factory for the loops: profile the window and record the
    program's own spans, then reduce both onto the run."""
    import jax
    from chipbench import devtrace
    from repro.core import trace as ptrace

    @contextlib.contextmanager
    def traced(run):
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        ptrace.enable()
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        live = {"profiler": True, "span": None}

        def begin():
            # the device trace keeps the window's first ``trace_seconds``:
            # past some millions of operations the profiler drops events
            live["span"] = jax.profiler.TraceAnnotation("bench.traced")
            live["span"].__enter__()
            run.trace_deadline = time.perf_counter() + float(
                run.traffic.get("trace_seconds", run.seconds))

        def stop():
            if live["span"] is not None:
                live["span"].__exit__(None, None, None)
                live["span"] = None
            if live["profiler"]:
                live["profiler"] = False
                jax.profiler.stop_trace()

        run.trace_begin, run.trace_stop = begin, stop
        s0 = ptrace.clock()
        try:
            yield
        finally:
            stop()
            run.trace_begin = run.trace_stop = None
            run.span_window = (s0, ptrace.clock())
            run.spans = ptrace.drain()
            ptrace.disable()
        run.device_trace = devtrace.reduce_file(
            devtrace.find_xplane(trace_dir))
    return traced


@contextlib.contextmanager
def untraced(run):
    yield


def run_cell(bench: dict, name: str, seed: int, seconds: float, trace: int,
             *, require_tpu: bool = True, step_factory=None, files=None,
             compile_cache: bool = True, work_dir: str = HERE,
             t_start: float = T_START, log=print) -> dict:
    """One run of cell ``name``; returns the result object. The tests run
    it on the CPU at small sizes: without the look for a chip, with
    ``files`` (entry, config, traffic) in place of the cell's files,
    without the persistent compile cache, and with checkpoints under a
    ``work_dir`` of their own."""
    import jax

    from chipbench import flops, loop

    entry, config, traffic = files or spec.load_cell(bench, name)
    devices = jax.devices()
    peaks = require_chips(devices, entry["chips"]) if require_tpu else {}
    from repro.core.engines import EngineConfig
    from repro.core.io_engine import resolve_backend
    from repro.launch.compile_cache import use_compile_cache
    if compile_cache:
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    ckpt_dir = os.path.join(work_dir, ".ckpt", name)
    trace_dir = os.path.join(work_dir, ".trace", name)
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    os.makedirs(ckpt_dir)
    io = {"backend": resolve_backend("auto"), "direct": EngineConfig().direct,
          "o_direct_holds": o_direct_holds(ckpt_dir)}
    log("io: " + json.dumps(io), flush=True)

    run = loop.Run(cell=name, config=config, traffic=traffic, seed=seed,
                   seconds=seconds, chips=entry["chips"], peaks=peaks, io=io)
    t = config["train"]
    run.flops_per_step = flops.train_flops_per_step(
        config["model"], t["batch"], t["seq_len"])
    cell = None
    try:
        cell = loop.Cell(run, ckpt_dir, step_factory)
        fn = loop.LOOPS[traffic["loop"]]
        fn(run, cell, t_start, traced_window(trace_dir) if trace else untraced)
    finally:
        if cell is not None:
            cell.close()
        shutil.rmtree(ckpt_dir, ignore_errors=True)
        shutil.rmtree(trace_dir, ignore_errors=True)

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.cell_metrics(bench, name, kind):
        value = spec.metric_reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": run.checks.correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if trace:
        dt = run.device_trace
        device.update(busy_s=dt.busy_s, window_s=dt.window_s)
        result["breakdown"] = {"device_ops": dt.top_ops,
                               "idle_gaps": dt.idle_gaps}
    log("run: " + json.dumps({
        "steps": run.steps, "saves": len(run.saves),
        "resumes": len(run.resume_s), "window_s": run.window_s,
        "each_resume_s": run.resume_s,
        "each_read_s": [getattr(rm, "read_seconds", None)
                        for rm in run.restores],
        "warm_resume_s": run.io.get("warm_resume_s"),
        "window_compiles": run.window_compiles,
        "stall_s": run.stall_s, "flops_per_step": run.flops_per_step,
        "after_window_s": run.after_s,
        "save_bytes": [[sm.total_bytes, sm.written_bytes] for sm in run.saves],
        "save_spans": _save_spans(run),
        "leaves_left_out": run.io.get("leaves_left_out"),
        "worst_leaves": run.io.get("worst_leaves"),
        "gaps": run.io.get("gaps"),
        "losses": run.io.get("losses")}), flush=True)
    result["checks"] = {k: [_num(v), _num(lim)]
                        for k, (v, lim) in run.checks.items.items()}
    return result


def _save_spans(run) -> list[dict]:
    """Per save of a traced window: the innermost-span sweep of the save's
    own thread, and the seconds with level-0 I/O in flight on any thread."""
    from chipbench import spans
    out = []
    for root in spans.roots(run.spans, "save", *run.span_window):
        attr = spans.attribute(run.spans, root)
        out.append({"wall_s": root.t1 - root.t0,
                    "sweep_s": {k: v for k, v in attr.items() if v},
                    "level0_io_s": spans.covered(run.spans, root,
                                                 spans.level0_io)})
    return out


def _num(x):
    """JSON has no infinity: a number that is not finite goes as text."""
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = spec.load_benchmark()
        import repro  # noqa: F401  (the system under test)
        result = run_cell(bench, args.workload, args.seed, args.seconds,
                          args.trace)
    except (NoChip, UnknownDevice, ImportError, FileNotFoundError) as e:
        print(f"run.py: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    for line in _check_lines(result["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def _check_lines(checks: dict) -> list[str]:
    return [f"check {k} {v!r} limit {lim!r}" for k, (v, lim) in checks.items()]


if __name__ == "__main__":
    sys.exit(main())
