"""The trace reduction on hand-built traces, and on one recorded here."""
from __future__ import annotations

import pytest

import xplane


def test_busy_is_the_union_of_ops_within_the_window():
    host = [("window", 1.0, 11.0)]
    dev = [[("a", 0.0, 2.0), ("b", 1.5, 3.0), ("c", 10.5, 12.0)]]
    red = xplane.reduce_events(host, dev)
    # [1, 3) and [10.5, 11) inside the window; the overlap counts once
    assert red["busy_s"] == pytest.approx(2.5)
    assert red["window_s"] == pytest.approx(10.0)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.75)


def test_busy_is_averaged_over_chips():
    host = [("window", 0.0, 10.0)]
    red = xplane.reduce_events(host, [[("a", 0, 4)], [("a", 0, 2)]])
    assert red["busy_s"] == pytest.approx(3.0)
    assert red["ops"]["a"] == pytest.approx(6.0)
    assert red["op_counts"]["a"] == 2


def test_kernel_time_sums_its_events():
    host = [("window", 0.0, 10.0)]
    dev = [[("k", 1.0, 1.5), ("f", 2.0, 3.0), ("k", 4.0, 4.25)]]
    red = xplane.reduce_events(host, dev)
    assert red["ops"]["k"] == pytest.approx(0.75)
    assert red["op_counts"]["k"] == 2
    bd = xplane.breakdown(red)
    assert bd["device_ops"][0] == ["f", 1.0]


def test_idle_goes_to_the_innermost_host_span():
    host = [("window", 0, 10), ("call", 1, 9), ("plan", 1, 3),
            ("engine", 3, 9), ("gen", 0, 1)]
    dev = [[("op", 2, 4), ("op", 5, 6), ("k", 5.5, 7)]]
    red = xplane.reduce_events(host, dev)
    assert red["idle"] == pytest.approx(
        {"gen": 1.0, "plan": 1.0, "engine": 3.0, "other": 1.0})
    # idle and busy make up the window
    assert sum(red["idle"].values()) + red["busy_s"] == pytest.approx(10)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce_events([("call", 0, 1)], [[]])


@pytest.mark.parametrize("hlo, short", [
    ("%fusion.186 = bf16[7680,28,28]{0,2,1:T(8,128)(2,1)} fusion(bf16[60000,"
     "28,28]{0,2,1:T(8,128)(2,1)S(1)} %get-tuple-element.923), kind=kCustom",
     "%fusion.186 fusion"),
    ("%while = (s32[]{:T(128)}, f32[10,128]{1,0:T(8,128)}) while((s32[]{:T("
     "128)}, f32[10,128]{1,0:T(8,128)}) %tuple.74), condition=%wide.region_36",
     "%while while"),
    ("%select_and_scatter.9 = f32[10,768,28,28,16]{1,4,0,3,2:T(8,128)} "
     "select-and-scatter(f32[10,768,28,28,16]{1,4,0,3,2:T(8,128)} %m)",
     "%select_and_scatter.9 select-and-scatter"),
    ("copy-start.3", "copy-start.3"),
])
def test_ops_are_named_by_result_and_opcode(hlo, short):
    assert xplane.short_name(hlo) == short


def test_recorded_trace_yields_the_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(xplane.PREFIX + "window"):
        with jax.profiler.TraceAnnotation(xplane.PREFIX + "engine"):
            jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    host, devices = xplane.load(str(tmp_path))
    names = {name for name, _, _ in host}
    assert {"window", "engine"} <= names
    red = xplane.reduce_events(host, devices)
    assert red["window_s"] > 0
