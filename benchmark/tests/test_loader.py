"""Everything ``BENCHMARK.json`` names exists, and the files agree with
each other: the harness is driven by data, so the data has to hold."""

import importlib
import json
import os

import pytest

from benchmark import models, optimizers, run
from benchmark.readers import mfu

BENCH = run.read_json(run.ROOT, "BENCHMARK.json")
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = [w["name"] for w in BENCH["workloads"]]


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_every_configuration_file_exists_and_is_used():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        conf = run.read_json(run.ROOT, c["file"])
        assert c["file"].startswith(tuple(BENCH["paths"]))
        assert conf["name"] == c["name"] and c["name"] in used
        assert conf["source"] == c["source"]
        for k in conf.get("pallas_kernels", []):
            importlib.import_module("benchmark.kernels." + k["kernel"])


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_every_configuration_names_a_family_and_a_recipe(entry):
    """What a record and an update rule are comes from files the
    configuration names: a family that says what a record is, or the
    keys the default records are made from; a recipe with its three
    parts and the keys it reads."""
    conf = run.read_json(run.ROOT, entry["file"])
    family = models.load(conf)
    for part in ("build", "criterion", "param_specs", "loss_sum"):
        assert callable(getattr(family, part)), part
    assert hasattr(family, "BLOCK_ROWS")
    assert callable(getattr(family, "make_records", None)) or \
        {"image", "classes"} <= set(conf)
    assert os.path.isfile(os.path.join(
        run.HERE, "optimizers", conf["optimizer"] + ".py"))
    recipe = optimizers.load(conf)
    for part in ("build", "first_gradient", "update"):
        assert callable(getattr(recipe, part)), part
    assert callable(recipe.update(conf))  # every key it reads is there


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_loads_and_agrees_with_its_entry(name):
    cell = run.load_cell(name)
    entry = next(w for w in BENCH["workloads"] if w["name"] == name)
    w = cell["workload"]
    assert (w["config"], w["traffic"], w["chips"], w["why"]) == \
        (entry["config"], entry["traffic"], entry["chips"], entry["why"])
    assert set(w["limits"]) >= {"loss_gap", "grad1_worst_leaf_gap",
                                "delta_worst_leaf_gap"}
    reported = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell["per_layer"]


def test_every_moves_names_an_end_to_end_metric_its_cells_report():
    for m in BENCH["per_layer"]:
        assert m["moves"] in E2E, m["name"]
        for cell in cells_of(m):
            assert cell in cells_of(E2E[m["moves"]]), (m["name"], cell)


def test_every_per_layer_metric_has_its_file_and_reader():
    for m in BENCH["per_layer"]:
        spec = run.read_json(run.HERE, "layer_metrics", m["name"] + ".json")
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert spec.get("workloads") == m.get("workloads")
        reader = importlib.import_module("benchmark.readers." + spec["reader"])
        assert callable(reader.read)


def test_an_unknown_device_kind_is_an_error():
    ctx = {"peaks": run.read_json(run.HERE, "peaks.json"),
           "device_kind": "TPU v5 lite"}
    assert mfu.peak(ctx, "bf16_flops_per_s") == 197e12
    assert mfu.peak(ctx, "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        mfu.peak(dict(ctx, device_kind="cpu"), "bf16_flops_per_s")


def test_no_accelerator_means_no_result(monkeypatch, capsys):
    with pytest.raises(SystemExit) as err:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert err.value.code not in (0, None)
    assert '"metrics"' not in capsys.readouterr().out
