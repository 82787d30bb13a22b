"""Shared helpers of the benchmark's tests: the paths and a small copy of
each cell that the CPU can run in seconds."""

from __future__ import annotations

import copy
import importlib.util
import json
import os
import sys
import tempfile

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (CHIP, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import spec  # noqa: E402

# The small copies run the program in float32, where it agrees with the
# float32 reference to about 1e-6 on every number (CPU run); at width 64
# bfloat16 rounds away most of an AdamW step and says nothing of the
# comparison. The faults read 1e-3 and more on the loss, 5e-2 and more on
# a leaf (CPU run), the control 6e-2 and more on the first gradient.
SMALL_LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-2, "update_gap": 1e-2}


def load_run_module():
    spec_ = importlib.util.spec_from_file_location(
        "chipbench_run", os.path.join(CHIP, "run.py"))
    mod = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(mod)
    return mod


def cell_files(name: str):
    """(entry, config, traffic) of cell ``name``: as BENCHMARK.json lists
    it, or, for a cell it does not list yet, of ``<config>.<traffic>``
    from its files."""
    bench = spec.load_benchmark()
    if any(w["name"] == name for w in bench["workloads"]):
        return spec.load_cell(bench, name)
    cfg, traffic_name = name.split(".", 1)
    with open(os.path.join(CHIP, "configs", f"{cfg}.json")) as f:
        config = json.load(f)
    with open(os.path.join(CHIP, "traffic", f"{traffic_name}.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(CHIP, "workloads", f"{name}.json")) as f:
        traffic.update(json.load(f)["params"])
    entry = {"name": name, "config": cfg, "traffic": traffic_name,
             "chips": 1}
    return entry, config, traffic


def small_files(name: str, *, save_every: int = 2):
    """The cell's (entry, config, traffic) at a size a test can hold."""
    bench = spec.load_benchmark()
    entry, config, traffic = cell_files(name)
    config = copy.deepcopy(config)
    m, t = config["model"], config["train"]
    m.update(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
             d_ff=96, vocab_size=512)
    # a row for each data replica of the cell's mesh
    t.update(batch=config.get("mesh", {}).get("data", 1), seq_len=1024,
             reference_rows_per_block=1)
    m["dtype"] = "float32"
    config["limits"] = dict(SMALL_LIMITS)
    traffic = dict(traffic)
    if "save_every_steps" in traffic:
        traffic["save_every_steps"] = save_every
    return bench, (entry, config, traffic)


def run_small(name: str, seed: int = 1234, seconds: float = 1.5,
              step_factory=None):
    bench, files = small_files(name)
    run_mod = load_run_module()
    with tempfile.TemporaryDirectory() as work:
        return run_mod.run_cell(bench, name, seed, seconds, 0,
                                require_tpu=False, compile_cache=False,
                                step_factory=step_factory, files=files,
                                work_dir=work, log=lambda *a, **k: None)
