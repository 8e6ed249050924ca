"""The check against its control and its faults, at sizes a CPU test can
hold: the reference one precision down in the program's place, and a run
driven with the timed path broken underneath, must come out not
correct."""
from __future__ import annotations

import json
import os

import ml_dtypes
import numpy as np
import pytest

import check
import gen
import reference as ref
from conftest import ROOT
from run import load_plugin


def _limits(cell):
    with open(os.path.join(ROOT, "bench", "limits", cell + ".json")) as f:
        return json.load(f)


def _cell(cell):
    """The cell's configuration and one call of its traffic."""
    with open(os.path.join(ROOT, "bench", "configs",
                           cell.split(".")[0] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "bench", "traffic",
                           cell.split(".")[1] + ".json")) as f:
        traffic = json.load(f)
    return config, gen.Traffic(config, traffic).call(2 ** 33 + 5, 0)


CELLS = ["paper_cnn_n10.epoch", "paper_mlp_n10.epoch"]


@pytest.mark.parametrize("cell", CELLS)
def test_bf16_planner_control_fails_greedy_wrong(cell):
    """At the cell's own n and T, the Theorem 3 rule on bfloat16 costs
    makes decisions that float32 resolution does not excuse."""
    config, call = _cell(cell)
    c = call.costs
    adj = ~np.eye(int(config["n"]), dtype=bool)
    dec = ref.greedy_rule(c.c_node, c.c_link, c.f_err, adj)
    ctl = ref.greedy_rule(c.c_node, c.c_link, c.f_err, adj,
                          dtype=ml_dtypes.bfloat16)
    assert ref.wrong_decisions(c.c_node, c.c_link, c.f_err, dec, dec) == 0
    wrong = ref.wrong_decisions(c.c_node, c.c_link, c.f_err, ctl, dec)
    assert wrong > _limits(cell)["greedy_wrong"]


@pytest.mark.parametrize("cell", CELLS)
def test_bf16_control_of_a_job_fails_one_of_its_numbers(cell):
    """The reference one precision down in the program's place, at the
    cell's own n, T, τ and pad: the first window and the round after it
    trained in bfloat16 against the reference."""
    config, call = _cell(cell)
    tau = int(config["tau"])
    model = load_plugin("models", config["model"])
    data = model.dataset(config)
    rounds = call.cells[:tau + 1]
    mo = ref.first_window(model, config, call.seed, data, rounds,
                          dtype="bfloat16",
                          precision=config["matmul_precision"])
    nums = check.training_numbers(call, {"processed": call.cells}, config,
                                  model, data, model_out=mo)
    lim = _limits(cell)
    assert any(nums[k] > lim[k] for k in nums), nums


@pytest.fixture
def broken_step(monkeypatch):
    """Swap the engine's per-device SGD step; the compiled programs that
    hold it are dropped before and after."""
    from repro.core import engine

    def use(make):
        monkeypatch.setattr(engine, "_device_step_fn", make)
        engine._scan_program.cache_clear()

    yield use
    monkeypatch.undo()
    engine._scan_program.cache_clear()


def _step(transform):
    import jax
    import jax.numpy as jnp

    from repro.models import mnist as mm

    def make(apply_fn, eta):
        def one(params, xb, yb, w, active):
            w = transform(w)
            loss, g = jax.value_and_grad(
                lambda p: mm.ce_loss(apply_fn(p, xb), yb, w))(params)
            scale = active * jnp.minimum(w.sum(), 1.0)
            return jax.tree_util.tree_map(
                lambda p, gg: p - eta * scale * gg, params, g), loss
        return one
    return make


def test_a_step_that_returns_its_state_unchanged_is_caught(
        run_cell, broken_step):
    import jax

    from repro.models import mnist as mm

    def make(apply_fn, eta):
        def one(params, xb, yb, w, active):
            loss = mm.ce_loss(apply_fn(params, xb), yb, w)
            return jax.tree_util.tree_map(lambda p: p, params), loss
        return one

    broken_step(make)
    res = run_cell("tiny_mlp.epoch")
    assert not res["correct"] and res["failed"] >= 1


def test_devices_that_keep_their_models_after_eq4_are_caught(
        run_cell, monkeypatch):
    from repro.core import engine

    monkeypatch.setattr(engine, "_sync", lambda W, wg, active: W)
    engine._scan_program.cache_clear()
    try:
        res = run_cell("tiny_mlp.epoch")
    finally:
        monkeypatch.undo()
        engine._scan_program.cache_clear()
    assert not res["correct"]
    row = res["checks"]["broadcast_loss_gap"]
    assert row["value"] > row["limit"]


def test_half_the_batch_left_out_is_caught(run_cell, broken_step):
    import jax.numpy as jnp

    # keep the first half of each device's samples; the mean is over them
    broken_step(_step(lambda w: w * (jnp.cumsum(w) <= jnp.ceil(w.sum() / 2))))
    res = run_cell("tiny_mlp.epoch")
    assert not res["correct"]


@pytest.mark.parametrize("cell", ["tiny_mlp.epoch", "tiny_cnn.epoch"])
def test_an_answer_altered_where_it_is_produced_is_caught(
        run_cell, monkeypatch, cell):
    from repro.core import movement as mv

    greedy = mv.greedy_linear

    def altered(traces, adj, **kw):
        plan = greedy(traces, adj, **kw)
        e = plan.edges
        # the first cell processed where it was collected is discarded
        k = int(np.nonzero(e.src == e.dst)[0][0])
        keep = np.arange(len(e.t)) != k
        r = plan.r.copy()
        r[e.t[k], e.src[k]] = 1.0
        edges = mv.PlanEdges(t=e.t[keep], src=e.src[keep], dst=e.dst[keep],
                             qty=e.qty[keep])
        return mv.MovementPlan(r=r, edges=edges, n=plan.n)

    monkeypatch.setattr(mv, "greedy_linear", altered)
    res = run_cell(cell)
    assert not res["correct"]
    assert res["checks"]["greedy_wrong"]["value"] >= 1
