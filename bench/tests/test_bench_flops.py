"""Operation and byte counts against hand counts, and the peak table."""
from __future__ import annotations

import pytest

import flops
from run import load_plugin

CNN, MLP = load_plugin("models", "cnn"), load_plugin("models", "mlp")


def test_forward_flops_match_hand_counts():
    # conv 28x28x16 over 5x5x1, conv 14x14x32 over 5x5x16, 1568-128, 128-10
    cnn = 2 * (28 * 28 * 16 * 25 + 14 * 14 * 32 * 400 + 1568 * 128 + 1280)
    assert CNN.forward_flops({}) == cnn == 6_048_768
    assert MLP.forward_flops({}) == 2 * (784 * 200 + 200 * 10) == 317_600


def test_job_flops_count_backward_and_eval():
    f = MLP.forward_flops({})
    assert flops.job_flops({"n_test": 1000}, MLP, 100, 2) == \
        3 * f * 100 + f * 1000 * 2


def test_train_mfu_reads_required_flops_over_the_spans():
    import importlib.util
    import os

    path = os.path.join(flops.HERE, "metrics", "train_mfu.py")
    spec = importlib.util.spec_from_file_location("train_mfu", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # the window: both spans and the generator's 0.5 s between them
    run = {"chips": 1, "window_s": 2.5, "model": MLP,
           "config": {"model": "mlp", "n_test": 1000},
           "device": {"peaks": {"flops_bf16": 1e12}},
           "calls": [{"samples": 100, "aggregations": 2, "span_s": 0.5},
                     {"samples": 300, "aggregations": 2, "span_s": 1.5}]}
    f = MLP.forward_flops({})
    want = 100.0 * (3 * f * 400 + f * 1000 * 4) / (2.5 * 1e12)
    assert mod.read(run) == pytest.approx(want)


def test_peaks_by_device_kind():
    pk = flops.peaks("TPU v5 lite")
    assert pk["flops_bf16"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
