"""The harness end to end on tiny cells on the CPU, the contract's shape
of BENCHMARK.json, and cells, models, traffic and metrics added by files
alone."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT, add_cell, add_config

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_keeps_the_contract_shape():
    spec = _spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    metrics = spec["end_to_end"] + spec["per_layer"]
    names = [x["name"] for x in spec["configs"] + spec["workloads"] + metrics]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    cells = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in cells and w in e2e[m["moves"]].get("workloads", [w])
    for w in spec["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        for m in metrics:
            if w["name"] in m.get("workloads", [w["name"]]):
                assert os.path.exists(os.path.join(
                    ROOT, "bench", "metrics", m["name"] + ".py")), m["name"]
        for sub, key in (("configs", "config"), ("traffic", "traffic")):
            assert os.path.exists(os.path.join(ROOT, "bench", sub,
                                               w[key] + ".json"))
        assert os.path.exists(os.path.join(ROOT, "bench", "limits",
                                           w["name"] + ".json"))


@pytest.mark.parametrize("cell", ["tiny_mlp.epoch", "tiny_cnn.epoch"])
def test_a_run_reports_its_metrics_and_is_correct(run_cell, cell):
    res = run_cell(cell)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert "setup_s" in res["metrics"]
    assert res["metrics"]["samples_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"
    assert all(r["value"] <= r["limit"] for r in res["checks"].values())


def test_a_traced_run_reports_per_layer_metrics(run_cell):
    res = run_cell("tiny_mlp.epoch", trace=1)
    assert {"plan_ms.epoch", "prep_ms.epoch", "engine_ms.epoch",
            "train_mfu", "device_idle.epoch"} <= set(res["metrics"])
    assert "samples_per_s" not in res["metrics"]
    assert res["device"]["window_s"] > 0 and "breakdown" in res


def test_files_alone_add_a_traffic_mix_a_cell_and_a_metric(
        mini_root, run_cell):
    bench = os.path.join(mini_root, "bench")
    with open(os.path.join(bench, "traffic", "epoch.json")) as f:
        mix = json.load(f)
    mix["cost_jitter"] = 0.05
    with open(os.path.join(bench, "traffic", "epoch_noisy.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench, "metrics", "jobs_in_window.py"),
              "w") as f:
        f.write("def read(run):\n    return len(run['calls'])\n")
    add_cell(mini_root, "tiny_mlp.epoch_noisy", "tiny_mlp", "epoch_noisy",
             "paper_mlp_n10.epoch")
    path = os.path.join(mini_root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["per_layer"].append({
        "name": "jobs_in_window", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "engine",
        "moves": "samples_per_s", "workloads": ["tiny_mlp.epoch_noisy"]})
    with open(path, "w") as f:
        json.dump(spec, f)
    res = run_cell("tiny_mlp.epoch_noisy", trace=1)
    assert res["correct"]
    assert res["metrics"]["jobs_in_window"]["value"] == res["attempted"]


def test_files_alone_add_a_model(mini_root, run_cell):
    # the program's linear model: a model module, a configuration, a cell
    # and its limits, and no harness file edited
    shutil.copy(os.path.join(ROOT, "bench", "tests", "fixtures", "linear.py"),
                os.path.join(mini_root, "bench", "models", "linear.py"))
    add_config(mini_root, "tiny_linear", "tiny_mlp", model="linear")
    add_cell(mini_root, "tiny_linear.epoch", "tiny_linear", "epoch",
             "paper_mlp_n10.epoch")
    res = run_cell("tiny_linear.epoch")
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert {"loss_gap", "test_loss_gap"} <= set(res["checks"])
    res = run_cell("tiny_linear.epoch", trace=1)
    assert res["correct"] and res["metrics"]["train_mfu"]["value"] > 0


def test_an_unknown_model_exits_non_zero_naming_the_known_ones(mini_root):
    add_config(mini_root, "tiny_nomodel", "tiny_mlp", model="no_such_model")
    add_cell(mini_root, "tiny_nomodel.epoch", "tiny_nomodel", "epoch",
             "paper_mlp_n10.epoch")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tiny_nomodel.epoch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=mini_root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and '"correct"' not in p.stdout
    assert "no_such_model" in p.stderr
    assert "'cnn'" in p.stderr and "'mlp'" in p.stderr


def test_the_command_refuses_to_report_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper_mlp_n10.epoch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "no TPU" in p.stderr


def test_the_command_needs_the_program_beside_it(tmp_path):
    # a checkout of the benchmark alone holds no system to measure
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper_mlp_n10.epoch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and '"correct"' not in p.stdout
