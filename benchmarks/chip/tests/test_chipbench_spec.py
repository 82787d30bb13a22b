"""BENCHMARK.json and the files it names: every cell, configuration,
traffic mix and metric loads by name, and the file keeps to its contract."""

import json
import math
import os
import re

import pytest

from _util import CHIP, ROOT, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = spec.load_benchmark()


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10


def test_names_units_and_keys():
    names = set()
    for kind, keys in (("configs", {"name", "source", "file", "reduced",
                                    "why"}),
                       ("workloads", {"name", "config", "traffic", "chips",
                                      "why"})):
        for e in BENCH[kind]:
            assert set(e) == keys, e
            assert NAME.match(e["name"]) and e["name"] not in names
            names.add(e["name"])
            assert 1 <= len(e["why"]) <= 200
    for kind in ("end_to_end", "per_layer"):
        for m in BENCH[kind]:
            assert NAME.match(m["name"]) and m["name"] not in names
            names.add(m["name"])
            assert UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
    assert {m["name"] for m in BENCH["end_to_end"]} == {
        "train_tokens_per_s", "save_stall_s", "resume_s", "setup_s"}


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_by_name(cell):
    entry, config, traffic = spec.load_cell(BENCH, cell)
    mesh = config.get("mesh", {})
    assert entry["chips"] in (1, 4)
    assert mesh.get("data", 1) * mesh.get("model", 1) == entry["chips"]
    assert traffic["loop"] in ("train_save", "resume")
    from chipbench.check import NUMBERS
    assert config["limits"] and set(config["limits"]) <= set(NUMBERS)
    assert os.path.exists(os.path.join(CHIP, "configs",
                                       f"{config['reference']}.py"))
    e2e = [m["name"] for m in spec.cell_metrics(BENCH, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = spec.cell_metrics(BENCH, cell, "per_layer")
    assert per_layer
    for m in per_layer:
        assert m["moves"] in e2e, (cell, m["name"])


@pytest.mark.parametrize("metric", sorted(
    {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    | {f[:-3] for f in os.listdir(os.path.join(CHIP, "metrics"))
       if f.endswith(".py")}))
def test_metric_reader_loads_by_name(metric):
    assert callable(spec.metric_reader(metric).read)


def test_every_listed_metric_has_a_reader():
    have = {f[:-3] for f in os.listdir(os.path.join(CHIP, "metrics"))}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert m["name"] in have


def test_per_layer_metrics():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert 1 <= len(m["layer"]) <= 200
        layers.setdefault(m["layer"], []).append(m["name"])
    assert "train step" in layers and "device" in layers


@pytest.mark.parametrize("cfg", [c["name"] for c in BENCH["configs"]])
def test_config_states_its_cut(cfg):
    """Every key that differs from the system's registry entry at published
    widths is listed in ``reduced``, and each entry is a depth."""
    from repro.configs import get_config
    entry = next(c for c in BENCH["configs"] if c["name"] == cfg)
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["reduced"] == entry["reduced"]
    assert config["source"] == entry["source"]
    for key in ("deployment", "assumed", "reference", "model", "train",
                "optimizer", "limits"):
        assert key in config
    registry = get_config(config["model"]["name"])
    differ = []
    for k, v in config["model"].items():
        have = getattr(registry, k)
        if isinstance(have, tuple):
            have = list(have)
        if isinstance(v, float) or isinstance(have, float):
            same = math.isclose(v, have)
        else:
            same = v == have
        if not same:
            differ.append(k)
    assert sorted(differ) == sorted(config["reduced"])
    assert set(config["reduced"]) <= {"num_layers"}
