"""Fault-injection plane + fault-tolerant engine (ISSUE-6):
FaultSchedule sampling/validation/views/composition, guarded
aggregation (clean no-op bitwise, NaN survival), quorum-gated sync
(carry-forward), cross-engine equivalence under identical fault
streams, crash == unannounced-churn composition, and the
AsyncEvaluator retry/backoff + multi-failure contract."""
import math

import numpy as np
import pytest

from repro.core import engine as eng
from repro.core import faults as fl
from repro.core import federated as F
from repro.core import movement as mv
from repro.core.costs import synthetic_costs
from repro.core.schedule import NetworkSchedule
from repro.core.topology import fully_connected
from repro.data import pipeline as pl
from repro.data.synthetic import make_image_dataset


def _setup(n=6, T=12, tau=4, seed=0):
    data = make_image_dataset(n_train=1200, n_test=400, seed=0)
    cfg = F.FedConfig(n=n, T=T, tau=tau, eta=0.05, model="mlp",
                      seed=seed)
    rng = np.random.default_rng(seed)
    traces = synthetic_costs(n, T, rng)
    adj = fully_connected(n)
    streams = pl.poisson_streams(n, T, data[1], rng=rng)
    plan = mv.greedy_linear(traces, adj)
    return cfg, data, traces, adj, plan, streams


def _run(engine, faults=None, guard=True, quorum=0.0, activity=None,
         **kw):
    cfg, data, traces, adj, plan, streams = _setup(**kw)
    return F.run_network_aware(cfg, data, traces, adj, plan,
                               streams=streams, activity=activity,
                               engine=engine, faults=faults,
                               guard=guard, quorum=quorum)


def _assert_hist_bitwise(ha, hb):
    assert ha["agg_round"] == hb["agg_round"]
    assert ha["test_acc"] == hb["test_acc"]
    assert ha["test_loss"] == hb["test_loss"]
    for a, b in zip(ha["device_loss"], hb["device_loss"]):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.asarray(ha["H_agg"]),
                                  np.asarray(hb["H_agg"]))


# ---------------------------------------------------------------------------
# FaultSchedule: sampling, validation, views, composition
# ---------------------------------------------------------------------------


def test_sample_deterministic_in_seed():
    # NaN payloads defeat == on the events, so compare a NaN-safe key
    def key(fs):
        return [(e.t, e.kind, e.device, repr(e.value))
                for e in fs.events]

    kw = dict(p_straggle=0.2, p_drop=0.2, p_crash=0.2, p_corrupt=0.2)
    a = fl.FaultSchedule.sample(20, 8, 5, rng=3, **kw)
    b = fl.FaultSchedule.sample(20, 8, 5, rng=3, **kw)
    assert key(a) == key(b) and len(a.events) > 0
    c = fl.FaultSchedule.sample(20, 8, 5, rng=4, **kw)
    assert key(a) != key(c)


def test_event_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        fl.FaultEvent(3, "meteor", 0)
    # upload faults only exist at window-last rounds
    with pytest.raises(ValueError, match="window-last"):
        fl.FaultSchedule(12, 4, 4, [fl.FaultEvent(2, "drop", 0)])
    fl.FaultSchedule(12, 4, 4, [fl.FaultEvent(3, "drop", 0)])  # ok
    # crashes may start anywhere
    fl.FaultSchedule(12, 4, 4, [fl.FaultEvent(2, "crash", 0)])
    with pytest.raises(ValueError, match="outside horizon"):
        fl.FaultSchedule(12, 4, 4, [fl.FaultEvent(12, "crash", 0)])
    with pytest.raises(ValueError, match="outside"):
        fl.FaultSchedule(12, 4, 4, [fl.FaultEvent(3, "drop", 4)])


def test_views_drop_wins_over_corrupt():
    fs = fl.FaultSchedule(8, 3, 4, [
        fl.FaultEvent(3, "corrupt", 0, float("nan")),
        fl.FaultEvent(3, "drop", 0),
        fl.FaultEvent(7, "corrupt", 1, float("nan"))])
    upl, cor = fs.engine_arrays()
    assert upl[3, 0] == 0.0
    # the dropped upload never arrives, so its NaN must not either
    assert cor[3, 0] == 1.0
    assert math.isnan(cor[7, 1]) and upl[7, 1] == 1.0
    assert fs.activity_mask().all()


def test_crash_outage_defaults_to_rest_of_window():
    fs = fl.FaultSchedule(8, 2, 4, [fl.FaultEvent(1, "crash", 0),
                                    fl.FaultEvent(5, "crash", 1, 1.0)])
    act = fs.activity_mask()
    assert not act[1:4, 0].any() and act[0, 0] and act[4:, 0].all()
    assert not act[5, 1] and act[6, 1]          # explicit 1-round outage
    assert fs.has_crashes and not fs.has_upload_faults
    assert fs.summary() == {"straggle": 0, "drop": 0, "crash": 2,
                            "corrupt": 0, "total": 2}


def test_compose_ands_crashes_into_schedule():
    n, T = 3, 8
    adj = fully_connected(n)
    fs = fl.FaultSchedule(T, n, 4, [fl.FaultEvent(1, "crash", 2)])
    sched = fs.compose(adj=adj)
    act = sched.activity()
    assert not act[1:4, 2].any() and act[:, :2].all()
    # links touching the crashed node go down with it
    assert not sched.adj_at(2)[2].any()
    # a fault-free schedule composes to the base unchanged
    empty = fl.FaultSchedule(T, n, 4)
    base = NetworkSchedule.constant(adj, T)
    assert empty.compose(base) is base
    with pytest.raises(ValueError, match="needs a schedule"):
        fs.compose()
    with pytest.raises(ValueError, match="network schedule"):
        fs.compose(NetworkSchedule.constant(adj, T + 1))


def test_make_faults_dispatch():
    assert fl.make_faults("none", 8, 4, 4, rate=0.5) is None
    assert fl.make_faults(None, 8, 4, 4, rate=0.5) is None
    assert fl.make_faults("drop", 8, 4, 4, rate=0.0) is None
    fs = fl.make_faults("drop", 40, 8, 4, rate=0.9, seed=1)
    assert fs.has_upload_faults and not fs.has_crashes
    mixed = fl.make_faults("mixed", 40, 8, 4, rate=0.8, seed=1)
    assert set(k for k, v in mixed.summary().items()
               if k in fl.FAULT_KINDS and v) >= {"drop", "crash"}
    with pytest.raises(ValueError, match="unknown fault kind"):
        fl.make_faults("meteor", 8, 4, 4, rate=0.5)


# ---------------------------------------------------------------------------
# engine tolerance
# ---------------------------------------------------------------------------


def test_empty_faults_guarded_is_bitwise_noop():
    clean = _run("scan")
    fs = fl.FaultSchedule(12, 6, 4)          # zero events, guard armed
    noop = _run("scan", faults=fs, guard=True, quorum=0.5)
    _assert_hist_bitwise(clean, noop)
    assert noop["agg_quorum_ok"] == [True, True, True]


def test_nan_corrupt_guarded_survives_unguarded_poisoned():
    ev = [fl.FaultEvent(t, "corrupt", d, float("nan"))
          for t in (3, 7, 11) for d in (0, 1)]
    fs = fl.FaultSchedule(12, 6, 4, ev)
    guarded = _run("scan", faults=fs, guard=True)
    clean = _run("scan")
    assert all(np.isfinite(a) for a in guarded["test_acc"])
    # survivors renormalize: 4 of 6 contribute at every window
    assert guarded["agg_survivors"] == [4.0, 4.0, 4.0]
    unguarded = _run("scan", faults=fs, guard=False)
    # one NaN reaches the reduction and the global never recovers
    assert not np.isfinite(unguarded["test_loss"][-1])
    assert clean["test_acc"][-1] > unguarded["test_acc"][-1]


def test_quorum_skip_carries_global_forward():
    n = 6
    ev = [fl.FaultEvent(7, "drop", d) for d in range(n)]
    fs = fl.FaultSchedule(12, n, 4, ev)
    h = _run("scan", faults=fs, guard=True, quorum=0.5)
    assert h["agg_quorum_ok"] == [True, False, True]
    assert h["agg_survivors"][1] == 0.0
    # the skipped window's eval sees the carried-forward global
    assert h["test_acc"][1] == h["test_acc"][0]
    assert h["test_loss"][1] == h["test_loss"][0]
    # quorum=0 accepts even an empty window (agg falls back to prev)
    h0 = _run("scan", faults=fs, guard=True, quorum=0.0)
    assert h0["agg_quorum_ok"] == [True, True, True]
    assert h0["test_acc"][1] == h0["test_acc"][0]


def _mixed_faults(T=12, n=6, tau=4):
    return fl.FaultSchedule(T, n, tau, [
        fl.FaultEvent(3, "corrupt", 0, float("nan")),
        fl.FaultEvent(3, "straggle", 1),
        fl.FaultEvent(5, "crash", 2),
        fl.FaultEvent(7, "drop", 3),
        fl.FaultEvent(11, "corrupt", 4, float("inf")),
    ])


def test_scan_matches_legacy_under_faults():
    fs = _mixed_faults()
    hl = _run("legacy", faults=fs, guard=True, quorum=0.3)
    hs = _run("scan", faults=fs, guard=True, quorum=0.3)
    assert hl["agg_round"] == hs["agg_round"]
    assert hl["agg_survivors"] == hs["agg_survivors"]
    assert hl["agg_quorum_ok"] == hs["agg_quorum_ok"]
    np.testing.assert_allclose(hs["test_acc"], hl["test_acc"],
                               atol=1e-6)
    np.testing.assert_allclose(hs["test_loss"], hl["test_loss"],
                               rtol=2e-5)
    np.testing.assert_allclose(np.asarray(hs["H_agg"]),
                               np.asarray(hl["H_agg"]), rtol=1e-6)


def test_batched_matches_scan_under_faults():
    fs = _mixed_faults()
    hs = _run("scan", faults=fs, guard=True, quorum=0.3)
    cfg, data, _, _, plan, streams = _setup()
    hb = F.run_network_aware_batched([cfg], data, [plan], streams=[streams],
                                     mesh=None, bucket="exact", faults=[fs],
                                     guard=True, quorum=0.3)[0]
    # the batched engine evaluates through the stacked AsyncEvaluator,
    # not in-scan: its test-loss mean rounds in another order (1 ulp
    # observed on the CPU), so losses match to 1e-6 (~8 f32 ulps, as
    # test_engine_batched.LOSS_RTOL) and the rest exactly
    assert hs["agg_round"] == hb["agg_round"]
    assert hs["test_acc"] == hb["test_acc"]
    np.testing.assert_array_equal(np.asarray(hs["H_agg"]),
                                  np.asarray(hb["H_agg"]))
    np.testing.assert_allclose(hb["test_loss"], hs["test_loss"],
                               rtol=1e-6, atol=0.0)
    np.testing.assert_allclose(np.stack(hb["device_loss"]),
                               np.stack(hs["device_loss"]),
                               rtol=1e-6, atol=0.0)
    assert hs["agg_survivors"] == hb["agg_survivors"]
    assert hs["agg_quorum_ok"] == hb["agg_quorum_ok"]


def test_crash_only_equals_activity_composition():
    # an unannounced crash must train/collect exactly like a churned
    # device nobody planned for: faults= is ANDed into activity
    fs = fl.FaultSchedule(12, 6, 4, [fl.FaultEvent(5, "crash", 2),
                                     fl.FaultEvent(8, "crash", 4, 2.0)])
    via_faults = _run("scan", faults=fs, guard=True)
    via_activity = _run("scan", activity=fs.activity_mask())
    _assert_hist_bitwise(via_faults, via_activity)


def test_checkpoint_resume_requires_scan_engine():
    cfg, data, traces, adj, plan, streams = _setup()
    with pytest.raises(ValueError, match="scan-engine"):
        F.run_network_aware(cfg, data, traces, adj, plan,
                            streams=streams, engine="legacy",
                            checkpoint_path="/tmp/nope.msgpack")


# ---------------------------------------------------------------------------
# AsyncEvaluator: retry-with-backoff + multi-failure reporting
# ---------------------------------------------------------------------------


def _tiny_eval_set():
    x = np.zeros((4, 3), np.float32)
    y = np.zeros(4, np.int32)
    return x, y


def test_async_evaluator_retries_transient_dispatch():
    import jax.numpy as jnp

    x, y = _tiny_eval_set()
    ev = eng.AsyncEvaluator(lambda p, xx: jnp.zeros((xx.shape[0], 10)),
                            x, y, retries=3, backoff=0.001)
    calls = {"n": 0}
    real = ev._fn

    def flaky(*args):
        calls["n"] += 1
        if calls["n"] <= 2:
            raise OSError("transient")
        return real(*args)

    ev._fn = flaky
    ev.submit({"w": np.zeros(3, np.float32)})
    losses, accs = ev.collect()              # survived two transients
    assert calls["n"] == 3 and len(losses) == 1
    assert np.isfinite(losses[0])


def test_async_evaluator_exhausted_retries_defer():
    x, y = _tiny_eval_set()

    def bad(p, xx):
        raise ValueError("permanent")

    ev = eng.AsyncEvaluator(bad, x, y, retries=2, backoff=0.001)
    ev.submit({"w": np.zeros(3, np.float32)})
    with pytest.raises(RuntimeError, match="1 submitted evaluation"):
        ev.collect()


def test_async_evaluator_lists_all_failures():
    x, y = _tiny_eval_set()
    ev = eng.AsyncEvaluator(lambda p, xx: None, x, y, retries=0,
                            backoff=0.0)
    ev._dispatch(lambda: (_ for _ in ()).throw(ValueError("first")))
    ev._dispatch(lambda: (_ for _ in ()).throw(TypeError("second")))
    with pytest.raises(RuntimeError) as ei:
        ev.collect()
    msg = str(ei.value)
    assert "2 submitted evaluation(s) failed" in msg
    assert "first" in msg and "second" in msg
    assert [type(e) for e in ei.value.failures] == [ValueError,
                                                    TypeError]
    assert isinstance(ei.value.__cause__, ValueError)


def test_async_evaluator_shutdown_idempotent_after_failure():
    x, y = _tiny_eval_set()

    def bad(p, xx):
        raise ValueError("boom")

    ev = eng.AsyncEvaluator(bad, x, y, retries=0, backoff=0.0)
    ev.submit({"w": np.zeros(3, np.float32)})
    with pytest.raises(RuntimeError):
        ev.shutdown()
    ev.shutdown()                            # cleared: now a no-op
    ev.shutdown()
