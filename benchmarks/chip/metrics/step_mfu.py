"""Model FLOPs of one training step (``chipbench.flops``, no recompute)
over the mean device time of the train-step program in the profiler trace
times the chip's bf16 peak, in percent."""

PROGRAM = "jit_train_step"


def read(run):
    dt = run.device_trace
    times = dt.module_s.get(PROGRAM) if dt is not None else None
    if not times:
        return None
    mean = sum(times) / len(times)
    return 100.0 * run.flops_per_step / (mean * run.peaks["bf16_flops"])
