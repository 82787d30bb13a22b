"""Reduction of a ``jax.profiler`` trace to device busy time and gaps.

Only the process that holds the chip can trace it, so a traced run wraps
its measured window in ``jax.profiler.trace`` and the benchmark's own loop
marks what the host is doing with ``jax.profiler.TraceAnnotation`` spans
named ``bench.*``. Those host spans and the device's operations share the
profiler's clock, which is what lets an idle gap on the device be named by
the host work that was under way.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.traced"     # the part of the measured window that is traced
ENCLOSING = {WINDOW, "bench.window"}


@dataclass
class Interval:
    name: str
    t0: int      # ns on the profiler's clock
    t1: int


@dataclass
class DeviceTrace:
    window_s: float
    busy_s: float                      # union of op intervals, mean over chips
    devices: int
    module_s: dict[str, list[float]] = field(default_factory=dict)
    top_ops: list = field(default_factory=list)     # [[name, seconds], ...]
    idle_gaps: list = field(default_factory=list)   # [[name, seconds], ...]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: int, b: int, w0: int, w1: int):
    a, b = max(a, w0), min(b, w1)
    return (a, b) if b > a else None


def host_spans(planes) -> list[Interval]:
    """``bench.*`` annotations from every host thread."""
    out = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in list(plane.lines):
            for ev in list(line.events):
                if ev.name.startswith("bench."):
                    out.append(Interval(ev.name, int(ev.start_ns),
                                        int(ev.end_ns)))
    return out


def innermost(spans: list[Interval], t: int) -> str:
    """Name of the latest-started host span (other than the windows) that
    covers ``t``; ``host_idle`` when the host was in none of them."""
    best = None
    for s in spans:
        if s.name not in ENCLOSING and s.t0 <= t < s.t1:
            if best is None or s.t0 > best.t0:
                best = s
    return best.name[len("bench."):] if best else "host_idle"


def reduce_planes(planes, top: int = 10) -> DeviceTrace:
    planes = list(planes)   # ProfileData hands out one-pass iterators
    spans = host_spans(planes)
    windows = [s for s in spans if s.name == WINDOW]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW} span")
    w0, w1 = windows[-1].t0, windows[-1].t1
    devices = [p for p in planes if p.name.startswith(DEVICE_PREFIX)
               and p.name[len(DEVICE_PREFIX):].isdigit()]
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    busy_total = 0.0
    op_time: dict[str, float] = {}
    module_s: dict[str, list[float]] = {}
    gaps: list[tuple[int, int]] = []
    for plane in devices:
        ops = []
        for line in list(plane.lines):
            if line.name == OPS_LINE:
                for ev in line.events:
                    c = _clip(int(ev.start_ns), int(ev.end_ns), w0, w1)
                    if c:
                        ops.append(c)
                        name = op_name(ev.name)
                        op_time[name] = op_time.get(name, 0.0) \
                            + (c[1] - c[0]) * 1e-9
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    if w0 <= int(ev.start_ns) < w1:
                        module_s.setdefault(_module_name(ev.name), []) \
                            .append(int(ev.duration_ns) * 1e-9)
        busy = union(ops)
        busy_total += sum(b - a for a, b in busy) * 1e-9
        prev = w0
        for a, b in busy:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        if w1 > prev:
            gaps.append((prev, w1))
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[innermost(spans, (a + b) // 2), (b - a) * 1e-9]
            for a, b in gaps[:top]]
    ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return DeviceTrace(window_s=(w1 - w0) * 1e-9,
                       busy_s=busy_total / len(devices),
                       devices=len(devices), module_s=module_s,
                       top_ops=[[k, v] for k, v in ops_top],
                       idle_gaps=idle)


def op_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _module_name(name: str) -> str:
    """``jit_train_step(42)`` -> ``jit_train_step``."""
    return name.split("(", 1)[0]


def reduce_file(path: str, top: int = 10) -> DeviceTrace:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, top)
