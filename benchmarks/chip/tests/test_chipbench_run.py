"""A run's last line, and what a run does with no chip."""

import json
import os
import shutil
import subprocess
import sys
import tempfile

import pytest

from _util import CHIP, ROOT, load_run_module, run_small, small_files

CONTRACT = ["correct", "attempted", "failed", "metrics", "device"]


def test_last_line_keys():
    res = run_small("stablelm-3b-8l.save_paced")
    assert list(res) == CONTRACT + ["checks"]     # the checks come last
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"train_tokens_per_s", "save_stall_s",
                                   "setup_s"}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["attempted"] > 0 and res["failed"] == 0
    for name, (value, limit) in res["checks"].items():
        assert value <= limit, name
    json.dumps(res, allow_nan=False)


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "stablelm-3b-8l.save_paced", "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "needs a TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's own files
    lacks the system under test."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".ckpt", ".trace",
                                                  "__pycache__"))
    p = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "metrics" not in p.stdout


@pytest.mark.parametrize("every,error", [(None, KeyError), (0, ValueError)])
def test_save_cadence_is_the_cells_to_set(every, error):
    """The traffic mix gives no save cadence: a cell without one, or with
    none at all, is refused before its window."""
    bench, (entry, config, traffic) = small_files(
        "stablelm-3b-8l.save_paced")
    traffic.pop("save_every_steps")
    if every is not None:
        traffic["save_every_steps"] = every
    with tempfile.TemporaryDirectory() as work, pytest.raises(error):
        load_run_module().run_cell(
            bench, entry["name"], 1, 1.0, 0, require_tpu=False,
            compile_cache=False, files=(entry, config, traffic),
            work_dir=work, log=lambda *a, **k: None)
