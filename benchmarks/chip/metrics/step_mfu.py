"""Model FLOPs of one training step of the global batch
(``chipbench.flops``, no recompute) over the mean device time of the
train-step program in the profiler trace (each device's run of it counts
once) times the bf16 peak of every chip in the cell's mesh, in percent."""

PROGRAM = "jit_train_step"


def read(run):
    dt = run.device_trace
    times = dt.module_s.get(PROGRAM) if dt is not None else None
    if not times:
        return None
    mean = sum(times) / len(times)
    peak = run.peaks["bf16_flops"] * run.chips
    return 100.0 * run.flops_per_step / (mean * peak)
