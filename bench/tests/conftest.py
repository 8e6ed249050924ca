"""Fixtures of the benchmark's tests: a copy of the benchmark beside the
repository's ``src/`` with tiny cells, run on the CPU."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

# tiny stand-ins for the real cells: the same configuration keys and
# traffic mixes, at sizes a CPU test can hold
TINY = {
    "tiny_mlp": ("paper_mlp_n10", dict(n=8, T=6, tau=3, n_train=2000,
                                       n_test=1000)),
    "tiny_cnn": ("paper_cnn_n10", dict(n=2, T=4, tau=2, n_train=400,
                                       n_test=1000, max_points=128)),
}
CELLS = {"tiny_mlp.epoch": "paper_mlp_n10.epoch",
         "tiny_cnn.epoch": "paper_cnn_n10.epoch"}


def add_cell(root, name, config, traffic, like):
    """Add a cell by files and BENCHMARK.json entries alone: the limits
    and the metrics' cell lists of the real cell ``like``."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": name, "config": config,
                              "traffic": traffic, "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(name)
    with open(path, "w") as f:
        json.dump(spec, f)
    shutil.copy(os.path.join(root, "bench", "limits", like + ".json"),
                os.path.join(root, "bench", "limits", name + ".json"))


def add_config(root, name, like, **over):
    """Add a configuration by a file and its BENCHMARK.json entry: the
    configuration ``like`` with the keys ``over`` changed."""
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", like + ".json")) as f:
        cfg = json.load(f)
    cfg.update(over)
    with open(os.path.join(bench, "configs", name + ".json"), "w") as f:
        json.dump(cfg, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": name, "source": "test",
                            "file": f"bench/configs/{name}.json",
                            "reduced": [], "why": "test"})
    with open(path, "w") as f:
        json.dump(spec, f)


@pytest.fixture(scope="session")
def mini_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    peaks = os.path.join(root, "bench", "peaks.json")
    with open(peaks) as f:
        table = json.load(f)
    table["kinds"]["cpu"] = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11,
                             "hbm_bytes": 1e9}
    with open(peaks, "w") as f:
        json.dump(table, f)
    for name, (base, over) in TINY.items():
        add_config(root, name, base, **over)
    for name, like in CELLS.items():
        config, traffic = name.split(".")
        add_cell(root, name, config, traffic, like)
    return root


@pytest.fixture
def run_cell(mini_root, monkeypatch, tmp_path):
    """run.main of a cell in the copy, on the CPU, with the persistent
    compile cache left off and JAX's settings restored afterwards."""
    import jax

    import run

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    before = jax.config.jax_persistent_cache_min_compile_time_secs

    def go(name, seed=12345678901, seconds=1.0, trace=0, root=mini_root):
        argv = ["--workload", name, "--seed", str(seed), "--seconds",
                str(seconds), "--trace", str(trace)]
        return run.main(argv, require_tpu=False, root=root)

    yield go
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)
