"""The reduction of the program's part of a trace, on hand-built events,
on one recorded here, and through a traced run of a tiny cell."""
from __future__ import annotations

import json
import os

import pytest

import progtrace


def _fog(name, a, b, **stats):
    return (name, a, b, stats)


def test_spans_outside_the_window_are_left_out_and_self_time_nests():
    bench = [("window", 1.0, 9.0), ("call", 1.0, 9.0)]
    fog = [_fog("train", 0.0, 2.0),                 # starts before
           _fog("train", 2.0, 6.0), _fog("train.stage", 2.5, 3.0, slots=8,
                                         samples=2),
           _fog("train.device", 3.0, 5.5),
           _fog("train", 6.0, 8.0), _fog("train.stage", 6.0, 7.0, slots=8,
                                         samples=6),
           _fog("prep", 8.5, 9.5)]                   # ends after
    red = progtrace.reduce_events(fog, bench, [], None)
    sp = red["spans"]
    assert set(sp) == {"train", "train.stage", "train.device"}
    assert sp["train"]["calls"] == 2
    assert sp["train"]["seconds"] == pytest.approx(6.0)
    assert sp["train"]["self_seconds"] == pytest.approx(6.0 - 0.5 - 2.5 - 1)
    assert sp["train.stage"]["slots"] == 16
    assert sp["train.stage"]["samples"] == 8
    assert red["calls"] == 1 and red["window_s"] == pytest.approx(8.0)
    assert red["scopes"] is None


def test_device_time_by_scope_clips_to_the_window_and_skips_containers():
    bench = [("window", 1.0, 11.0)]
    maps = {"jit_train": [{"fusion.1": "local_sgd", "fusion.2": "eval"}],
            "jit__gather_rows": [{"fusion": "gather"}]}
    w = "%while = (f32[2]) while(f32[2] %t), condition=%c, body=%b"
    cond = "%cond.3 = (f32[2]) conditional(pred[] %p), branch_computations"
    chip = [("jit__gather_rows(7)", "%fusion = f32[4] fusion(f32[9] %x)",
             0.0, 2.0),                                   # half inside
            ("jit_train(5)", w, 2.0, 10.0),
            ("jit_train(5)", "%fusion.1 = f32[2] fusion(f32[2] %a)",
             2.0, 5.0),
            ("jit_train(5)", cond, 5.0, 7.0),
            ("jit_train(5)", "%fusion.2 = f32[] fusion(f32[2] %a)",
             5.0, 7.0),
            ("jit_train(5)", "%copy.3 = f32[2] copy(f32[2] %a)", 7.0, 8.0),
            ("jit_train(5)", "%fusion.1 = f32[2] fusion(f32[2] %a)",
             10.5, 12.0)]                                 # half inside
    red = progtrace.reduce_events([], bench, [chip], maps)
    assert red["scopes"] == pytest.approx(
        {"gather": 1.0, "local_sgd": 3.5, "eval": 2.0})
    # containers hold the ops above: neither scoped nor unscoped
    assert red["unscoped_ops"] == pytest.approx({"%copy.3 copy": 1.0})


def test_the_map_that_knows_the_module_s_ops_is_chosen():
    bench = [("window", 0.0, 10.0)]
    maps = {"jit_train": [{"fusion.9": "eval"},
                          {"fusion.1": "local_sgd", "fusion.2": "local_sgd"}]}
    chip = [("jit_train(5)", "%fusion.1 = f32[2] fusion()", 0.0, 1.0),
            ("jit_train(5)", "%fusion.2 = f32[2] fusion()", 1.0, 2.0)]
    red = progtrace.reduce_events([], bench, [chip], maps)
    assert red["scopes"] == pytest.approx({"local_sgd": 2.0})


def test_idle_goes_to_the_innermost_fog_span_then_the_bench_span():
    bench = [("window", 0.0, 10.0), ("call", 0.0, 9.0), ("gen", 9.0, 10.0),
             ("engine", 4.0, 9.0)]
    fog = [_fog("prep", 1.0, 4.0), _fog("prep.route", 1.0, 2.0),
           _fog("train", 4.0, 9.0), _fog("train.device", 5.0, 8.0)]
    chip = [("m(1)", "op", 6.0, 7.0)]
    red = progtrace.reduce_events(fog, bench, [chip], {})
    assert red["idle"] == pytest.approx(
        {"fog:prep.route": 1.0, "fog:prep": 2.0, "fog:train": 2.0,
         "fog:train.device": 2.0, "bench:call": 1.0, "bench:gen": 1.0})
    assert sum(red["idle"].values()) == pytest.approx(10.0 - 1.0)


def test_idle_outside_every_span_is_other():
    bench = [("window", 0.0, 4.0)]
    red = progtrace.reduce_events([_fog("prep", 1.0, 2.0)], bench,
                                  [[("m(1)", "op", 3.0, 4.0)]], None)
    assert red["idle"] == pytest.approx({"fog:prep": 1.0, "other": 2.0})


@pytest.mark.parametrize("op_name, scope", [
    ("jit(train)/while/body/closed_call/local_sgd/dot_general", "local_sgd"),
    ("jit(train)/while/body/transpose(jvp(local_sgd))/mul", "local_sgd"),
    ("jit(train)/while/body/gather/jit(_take)/gather", "gather"),
    ("jit(train)/while/body/cond/branch_1_fun/aggregate/gather", "aggregate"),
    ("jit(train)/while/body/cond/branch_1_fun/eval/reduce_sum", "eval"),
    ("jit(_gather_rows)/gather/gather", "gather"),
    # a primitive named like a scope is not a scope
    ("jit(train)/while/body/gather", None),
    ("jit(train)/while/body/add", None),
])
def test_scope_of_an_op_name(op_name, scope):
    assert progtrace.scope_of(op_name) == scope


def test_hlo_scopes_reads_instruction_metadata():
    text = "\n".join([
        "%fused_computation.3 (p: f32[2]) -> f32[2] {",
        '  ROOT %add.1 = f32[2] add(%p, %p), metadata={op_name='
        '"jit(train)/while/body/aggregate/add"}',
        "}",
        "  %fusion.15 = bf16[256,128]{1,0} fusion(%broadcast.34), kind=kLoop,"
        ' metadata={op_name="jit(f)/while/body/closed_call/gather/jit(_take)'
        '/gather" stack_frame_id=3}',
        "  %copy.18 = f32[128,128]{1,0} copy(%c0.1)",
        '  %while = (s32[]) while(%t), metadata={op_name="jit(f)/while"}'])
    assert progtrace.hlo_scopes(text) == {"add.1": "aggregate",
                                          "fusion.15": "gather"}


def test_live_scope_maps_read_the_process_s_executables():
    import jax.numpy as jnp

    from repro.core import engine

    x = jnp.arange(12.0).reshape(6, 2)
    engine._gather_rows(x, jnp.asarray([[1, 2]], jnp.int32))
    maps = progtrace.live_scope_maps({"jit__gather_rows"})
    assert set(maps) == {"jit__gather_rows"}
    assert {s for m in maps["jit__gather_rows"] for s in m.values()} \
        == {"gather"}


def test_recorded_trace_yields_fog_spans_with_counters(tmp_path):
    import jax
    import jax.numpy as jnp

    import xplane
    from repro.core import monitoring

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(xplane.PREFIX + "window"):
        with jax.profiler.TraceAnnotation(xplane.PREFIX + "call"):
            with monitoring.span("train.stage", slots=6) as sp:
                jnp.ones((8, 8)).sum().block_until_ready()
                sp.count(samples=4)
    jax.profiler.stop_trace()
    path = progtrace.newest(str(tmp_path))
    red = progtrace.reduce_file(path, scope_maps={})
    assert red["calls"] == 1
    row = red["spans"]["train.stage"]
    assert row["calls"] == 1 and row["slots"] == 6 and row["samples"] == 4


HOST_METRICS = ("solve_ms.epoch", "route_ms.epoch", "stage_ms.epoch",
                "engine_host_ms.epoch", "slot_fill.epoch", "h2d_mb.epoch")


def test_a_traced_run_reports_the_program_s_metrics(run_cell, monkeypatch,
                                                    mini_root):
    import run

    samples = {}
    job = run.System.run

    def counted(self, call, span):
        out = job(self, call, span)
        samples[call.index] = out["samples"]
        return out

    monkeypatch.setattr(run.System, "run", counted)
    res = run_cell("tiny_mlp.epoch", trace=1)
    assert res["correct"]
    got = res["metrics"]
    assert set(HOST_METRICS) <= set(got)
    # no device trace on the CPU: the scope metrics stay silent
    assert not {"gather_device_ms.epoch", "sgd_device_ms.epoch"} & set(got)
    with open(os.path.join(mini_root, "bench", "configs",
                           "tiny_mlp.json")) as f:
        cfg = json.load(f)
    k = res["attempted"]
    slots = cfg["T"] * cfg["n"] * cfg["max_points"]
    window = sum(samples[i] for i in range(1, k + 1))
    assert got["slot_fill.epoch"]["value"] == pytest.approx(
        100.0 * window / (k * slots))
    # idx, yb, w at 12 B a slot; counts and activity 4 B a cell; is_agg
    per_job = 12 * slots + 8 * cfg["T"] * cfg["n"] + cfg["T"]
    assert got["h2d_mb.epoch"]["value"] == pytest.approx(per_job / 1e6)
    assert all(got[m]["value"] > 0 for m in HOST_METRICS)
