"""The program's spans and counters (``core.monitoring``): nesting, self
time, counter sums and reset; a span's place in a profiler trace; the
engine's spans on every path; the device scopes of the scan programs."""
import glob
import os
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import engine as eng
from repro.core import federated as F
from repro.core import hierarchy as hr
from repro.core import monitoring
from repro.core import movement as mv
from repro.core.costs import synthetic_costs
from repro.core.topology import fully_connected
from repro.data import pipeline as pl
from repro.data.synthetic import make_image_dataset

SCOPES = ("gather", "local_sgd", "aggregate", "eval")


@pytest.fixture(autouse=True)
def _fresh_totals():
    monitoring.reset()
    yield
    monitoring.reset()


def test_span_totals_nesting_self_time_and_counters():
    with monitoring.span("outer", items=3) as sp:
        time.sleep(0.02)
        with monitoring.span("inner") as inner:
            time.sleep(0.03)
            inner.count(bytes=10)
        with monitoring.span("inner", bytes=5):
            pass
        sp.count(done=1)
    tot = monitoring.totals()
    assert set(tot) == {"outer", "inner"}
    assert tot["outer"]["calls"] == 1 and tot["inner"]["calls"] == 2
    assert tot["outer"]["items"] == 3 and tot["outer"]["done"] == 1
    assert tot["inner"]["bytes"] == 15
    assert tot["outer"]["seconds"] >= 0.05
    # self time leaves out the time of the spans opened inside it
    assert tot["outer"]["self_seconds"] == pytest.approx(
        tot["outer"]["seconds"] - tot["inner"]["seconds"])
    assert tot["inner"]["self_seconds"] == tot["inner"]["seconds"]
    assert tot["outer"]["self_seconds"] >= 0.015


def test_totals_is_a_copy_and_reset_clears():
    with monitoring.span("a", n=1):
        pass
    snap = monitoring.totals()
    snap["a"]["calls"] = 99
    assert monitoring.totals()["a"]["calls"] == 1
    monitoring.reset()
    assert monitoring.totals() == {}


def test_a_span_that_raises_is_still_counted_and_closed():
    with pytest.raises(RuntimeError):
        with monitoring.span("outer"):
            with monitoring.span("boom"):
                raise RuntimeError("x")
    tot = monitoring.totals()
    assert tot["boom"]["calls"] == 1 and tot["outer"]["calls"] == 1
    assert tot["outer"]["self_seconds"] == pytest.approx(
        tot["outer"]["seconds"] - tot["boom"]["seconds"])
    # the stack unwound: no span is left open
    assert monitoring._OPEN.get() is None


def test_span_lands_in_the_trace_with_its_counters(tmp_path):
    from jax.profiler import ProfileData

    x = jnp.ones((32, 32))
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("outer"):
        with monitoring.span("train.stage", slots=12) as sp:
            (x @ x).block_until_ready()
            sp.count(samples=7)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("outer", "fog:train.stage"):
                    events[e.name] = (e.start_ns, e.end_ns, dict(e.stats))
    (a0, a1, _), (b0, b1, stats) = events["outer"], events["fog:train.stage"]
    assert stats["slots"] == 12 and stats["samples"] == 7
    # the same clock: the span lies inside the enclosing annotation
    assert a0 <= b0 <= b1 <= a1


def _setup(n=4, T=6, tau=3, seed=0):
    data = make_image_dataset(n_train=600, n_test=200, seed=0)
    cfg = F.FedConfig(n=n, T=T, tau=tau, eta=0.05, model="mlp", seed=seed)
    rng = np.random.default_rng(seed)
    traces = synthetic_costs(n, T, rng)
    adj = fully_connected(n)
    streams = pl.poisson_streams(n, T, data[1], rng=rng)
    plan = mv.greedy_linear(traces, adj)
    return cfg, data, traces, adj, plan, streams


def test_scan_path_spans_and_counters():
    cfg, data, traces, adj, plan, streams = _setup()
    monitoring.reset()
    prep = F._prepare_streams(cfg, data, plan, streams, None, None)
    F.run_network_aware(cfg, data, traces, adj, plan, prepared=prep,
                        engine="scan")
    tot = monitoring.totals()
    for name in ("prep", "prep.route", "train", "train.init",
                 "train.history", "train.stage", "train.device",
                 "train.readback"):
        assert tot[name]["calls"] == 1, name
    processed, P = prep[1], prep[3]
    samples = sum(len(ix) for row in processed for ix in row)
    # the slots executed: packed chunk rows where they are fewer than
    # the dense (T, n, P) slab's
    C = pl.PACKED_CHUNK
    rows = max(sum(-(-len(ix) // C) for ix in row) for row in processed)
    R = 1 << (max(rows, 1) - 1).bit_length()
    packed = R * C < cfg.n * P
    slots = cfg.T * R * C if packed else cfg.T * cfg.n * P
    st = tot["train.stage"]
    assert st["packed"] == int(packed)
    assert st["slots"] == slots
    assert st["samples"] == samples
    # idx, yb, w (12 B a slot); the row owners (4 B a packed row);
    # counts and activity (4 B a cell); is_agg
    assert st["h2d_bytes"] == 12 * slots + 4 * cfg.T * R * packed \
        + 8 * cfg.T * cfg.n + cfg.T
    route = tot["prep.route"]
    assert route["processed"] == samples
    assert route["collected"] == sum(len(ix) for row in streams.collected
                                     for ix in row)
    # the train span holds its children
    kids = sum(tot[k]["seconds"] for k in
               ("train.init", "train.history", "train.stage",
                "train.device", "train.readback"))
    assert tot["train"]["self_seconds"] == pytest.approx(
        tot["train"]["seconds"] - kids)


def test_planner_spans_count_edges():
    cfg, data, traces, adj, plan, streams = _setup()
    monitoring.reset()
    greedy = mv.greedy_linear(traces, adj)
    D = np.full((cfg.T, cfg.n), 5.0)
    mv.repair_capacities(greedy, traces, adj, D)
    tot = monitoring.totals()
    assert tot["plan.greedy"]["edges"] == len(greedy.edges.t)
    assert tot["plan.repair"]["calls"] == 1


def test_flat_routing_counts_what_it_keeps():
    cfg, data, traces, adj, plan, streams = _setup()
    flat = pl.flat_from_streams(streams)
    monitoring.reset()
    out = pl.apply_movement_flat(flat, plan, np.random.default_rng(0))
    route = monitoring.totals()["prep.route"]
    assert route["calls"] == 1
    assert route["collected"] == flat.idx.shape[0]
    assert route["processed"] == out.idx.shape[0]


def test_hierarchical_path_records_the_engine_spans():
    cfg, data, traces, adj, plan, streams = _setup()
    tree = hr.TierTree.balanced(cfg.n, (2, 1), (3, 6))
    monitoring.reset()
    F.run_network_aware(cfg, data, traces, adj, plan, streams=streams,
                        hierarchy=tree)
    tot = monitoring.totals()
    for name in ("train.stage", "train.device", "train.readback",
                 "train.tiers"):
        assert tot[name]["calls"] == 1, name
        assert tot[name]["seconds"] > 0.0, name
    assert tot["train.stage"]["slots"] > 0


def test_batched_path_records_the_engine_spans():
    cfg, data, traces, adj, plan, streams = _setup()
    eng.reset_staged_cache()
    monitoring.reset()
    F.run_network_aware_batched([cfg], data, [plan], streams=[streams],
                                mesh=None, bucket="exact")
    tot = monitoring.totals()
    for name in ("train.stage", "train.device", "train.eval",
                 "train.readback"):
        assert tot[name]["calls"] == 1, name
        assert tot[name]["seconds"] > 0.0, name


def _scopes_of(hlo_text: str):
    """The scopes each op_name path of an HLO text passes through."""
    seen = []
    for op in re.findall(r'op_name="([^"]*)"', hlo_text):
        parts = op.split("/")[:-1]
        hit = [s for s in SCOPES if any(
            s in re.findall(r"[A-Za-z_][A-Za-z0-9_]*", p) for p in parts)]
        seen.append(hit)
    return seen


@pytest.mark.parametrize("kind", ["scan", "hierarchical"])
def test_programs_carry_the_four_scopes_unnested(kind):
    n, T, P = 4, 4, 8
    params, apply_fn = eng.make_model("mlp", jax.random.PRNGKey(0))
    x_tr = jnp.zeros((32, 28, 28, 1), jnp.float32)
    args = (eng._stack(params, n), params, x_tr, None,
            jnp.zeros((T, n, P), jnp.int32), jnp.zeros((T, n, P), jnp.int32),
            jnp.zeros((T, n, P)), jnp.zeros((T, n)), jnp.ones((T, n)),
            jnp.asarray([False, True] * 2), jnp.zeros((16, 28, 28, 1)),
            jnp.zeros(16, jnp.int32))
    if kind == "scan":
        fn = eng._scan_program(apply_fn, 0.1, False)
    else:
        tree = hr.TierTree.balanced(n, (2, 1), (2, 4))
        fp = tree.fingerprint()
        eng._HIER_SPECS[fp] = eng._HierSpec(
            group_ids=tree.parents, num_groups=tree.group_counts,
            anc=tree.ancestors())
        fn = eng._scan_program(apply_fn, 0.1, False, tree_fp=fp)
        args = args + (jnp.asarray(tree.level_rounds(T)),)
    text = fn.lower(*args).compile().as_text()
    # the persistent compile cache keys a program by its name and its
    # computation, not by op metadata: scoped programs have names of
    # their own
    name = {"scan": "jit_fog_scan", "hierarchical": "jit_fog_hier_scan"}
    assert text.startswith(f"HloModule {name[kind]},")
    seen = _scopes_of(text)
    assert {s for hit in seen for s in hit} == set(SCOPES)
    assert all(len(hit) <= 1 for hit in seen)


@pytest.mark.parametrize("shape", [(6, 4), (6, 2, 2)])
def test_prestaged_gather_is_its_own_scoped_program(shape):
    """Rows of the (N, F) view: an image array gathers as flat rows."""
    x = jnp.arange(24.0).reshape(shape)
    idx = jnp.asarray([[0, 5], [2, 2]], jnp.int32)
    got = eng._gather_rows(x, idx)
    assert got.shape == (2, 2, 4)
    np.testing.assert_array_equal(got,
                                  np.asarray(x).reshape(6, -1)[np.asarray(idx)])
    text = eng._gather_rows.lower(x, idx).compile().as_text()
    assert {s for hit in _scopes_of(text) for s in hit} == {"gather"}
