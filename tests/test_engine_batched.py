"""Scenario-batched engine vs the per-point scan path.

History equivalence across a shape bucket — mixed (T, n) shapes
padded with phantom rounds/devices, churn schedules, mixed replan modes
— plus the program-cache guarantee (a sweep compiles at most one
program per shape bucket), the shape-bucketing policy and its
once-per-sweep inflation warning, and the stacked AsyncEvaluator.

At MATCHED staging (the per-point run padded to the same bucket P) the
aggregation schedule, H weights and accuracies are exact and losses
agree to LOSS_RTOL (see ``_assert_matches``); with each point's exact P
the padded reductions associate differently, so per-device losses get a
looser tolerance there.
"""
import copy
import dataclasses
import warnings

import numpy as np
import pytest

from repro.core import engine as eng
from repro.core import federated as F
from repro.core import movement as mv
from repro.core.costs import synthetic_costs
from repro.core.topology import fully_connected
from repro.data import pipeline as pl
from repro.data.synthetic import make_image_dataset


def _setup(n=6, T=12, tau=4, p_exit=0.0, p_entry=0.0, seed=0,
           max_points=0):
    data = make_image_dataset(n_train=1200, n_test=400, seed=0)
    cfg = F.FedConfig(n=n, T=T, tau=tau, eta=0.05, model="mlp", seed=seed,
                      p_exit=p_exit, p_entry=p_entry,
                      max_points=max_points)
    rng = np.random.default_rng(seed)
    traces = synthetic_costs(n, T, rng)
    adj = fully_connected(n)
    streams = pl.poisson_streams(n, T, data[1], rng=rng)
    plan = mv.greedy_linear(traces, adj)
    activity = F.churn_activity(cfg, rng) if (p_exit or p_entry) else None
    return cfg, data, plan, streams, activity


def _scan(setup):
    cfg, data, plan, streams, activity = setup
    return F.run_network_aware(cfg, data, None, None, plan,
                               streams=copy.deepcopy(streams),
                               activity=activity, engine="scan")


def _batched(setups, data, **kw):
    return F.run_network_aware_batched(
        [s[0] for s in setups], data, [s[2] for s in setups],
        streams=[copy.deepcopy(s[3]) for s in setups],
        activities=[s[4] for s in setups], **kw)


def _assert_bitwise(h_ref, h_bat):
    assert h_ref["agg_round"] == h_bat["agg_round"]
    assert h_ref["test_acc"] == h_bat["test_acc"]
    assert h_ref["test_loss"] == h_bat["test_loss"]
    np.testing.assert_array_equal(np.stack(h_ref["device_loss"]),
                                  np.stack(h_bat["device_loss"]))
    np.testing.assert_array_equal(np.stack(h_ref["H_agg"]),
                                  np.stack(h_bat["H_agg"]))


# Two programs that train the same parameters still EVALUATE them in
# differently fused programs: the scan engine's in-scan eval, and the
# batched engine's stacked vmapped AsyncEvaluator dispatch, whose
# reduction order also moves with the stack extent S. The test-set mean
# loss then rounds differently (observed on the CPU: 1 ulp, ~1e-7
# relative), and an accelerator's fusion can reorder the training
# reductions too. Losses therefore compare to LOSS_RTOL = 1e-6, about 8
# f32 ulps; the per-device losses are read off the parameters each
# round, so they bound the parameter drift as well. Everything the
# reduction order cannot touch stays exact: the aggregation schedule,
# accuracies (400 test images, a quantum of 0.0025) and H weights.
LOSS_RTOL = 1e-6


def _assert_matches(h_ref, h_bat, device_loss_rtol=LOSS_RTOL,
                    device_loss_atol=0.0):
    assert h_ref["agg_round"] == h_bat["agg_round"]
    assert h_ref["test_acc"] == h_bat["test_acc"]
    np.testing.assert_array_equal(np.stack(h_ref["H_agg"]),
                                  np.stack(h_bat["H_agg"]))
    np.testing.assert_allclose(h_bat["test_loss"], h_ref["test_loss"],
                               rtol=LOSS_RTOL, atol=0.0)
    np.testing.assert_allclose(np.stack(h_bat["device_loss"]),
                               np.stack(h_ref["device_loss"]),
                               rtol=device_loss_rtol,
                               atol=device_loss_atol)


# ---------------------------------------------------------------------------
# bucketing policy
# ---------------------------------------------------------------------------


def test_bucket_size_pow2_and_exact():
    assert [pl.bucket_size(v) for v in (1, 2, 3, 5, 8, 9, 100)] == \
        [1, 2, 4, 8, 8, 16, 128]
    assert pl.bucket_size(7, "exact") == 7
    with pytest.raises(ValueError):
        pl.bucket_size(4, "fib")


def test_bucket_rounds_buckets_window_count():
    # tau-aligned horizons with a pow2 window count pad ZERO rounds
    assert pl.bucket_rounds(20, 5) == 20          # 4 windows, already pow2
    assert pl.bucket_rounds(40, 5) == 40
    # otherwise the WINDOW count is bucketed (always a tau multiple)
    assert pl.bucket_rounds(10, 4) == 16          # 3 windows -> 4
    assert pl.bucket_rounds(10, 4, "exact") == 12  # just the tau multiple
    # ...unless the bucket would inflate the horizon beyond the cap:
    # padded windows still execute, so distant shapes keep exact sizes
    assert pl.bucket_rounds(24, 5) == 25          # 5 -> 8 is 1.6x: capped
    assert pl.bucket_rounds(100, 10) == 100       # 10 -> 16 is 1.6x: capped


def test_bucket_size_inflation_cap():
    assert pl.bucket_size(6, max_inflation=4 / 3) == 8     # 1.33x: ok
    assert pl.bucket_size(5, max_inflation=4 / 3) == 5     # 1.6x: capped
    assert pl.bucket_size(20, max_inflation=4 / 3) == 20   # 32 is 1.6x


def test_pad_size_bucket_policy():
    processed = [[np.arange(3), np.arange(9)]]
    assert pl.pad_size(processed) == 9
    assert pl.pad_size(processed, bucket="pow2") == 16
    assert pl.pad_size(processed, requested=20, bucket="pow2") == 32


def test_pad_batches_bucket_policy():
    x = np.zeros((10, 2, 2), np.float32)
    y = np.arange(10, dtype=np.int32)
    xb, yb, w = pl.pad_batches([np.arange(5)], x, y, 5, bucket="pow2")
    assert xb.shape[1] == yb.shape[1] == w.shape[1] == 8
    assert w.sum() == 5


def test_padding_inflation_warns_once_per_sweep():
    y = np.arange(64, dtype=np.int32)
    small = [[np.arange(2)] for _ in range(4)]      # P=2 per round
    big = [[np.arange(60)] for _ in range(4)]       # P=60 -> bucket 64
    act = [np.ones((4, 1))] * 3
    pl.reset_padding_warnings()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        # two inflated scenarios in one sweep -> ONE warning
        pl.stage_scenario_batch([small, small, big], y, act, tau=2)
        inflation = [w for w in rec
                     if "shape bucket pads" in str(w.message)]
        assert len(inflation) == 1
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        pl.stage_scenario_batch([small, small, big], y, act, tau=2)
        assert not [w for w in rec
                    if "shape bucket pads" in str(w.message)]
    pl.reset_padding_warnings()                     # new sweep: warns again
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        pl.stage_scenario_batch([small, big], y, act[:2], tau=2)
        assert [w for w in rec if "shape bucket pads" in str(w.message)]


def test_stage_scenario_batch_shapes_and_phantoms():
    y = np.arange(64, dtype=np.int32)
    p1 = [[np.arange(3), np.arange(2)] for _ in range(6)]   # n=2, T=6
    p2 = [[np.arange(4)] for _ in range(4)]                 # n=1, T=4
    act = [np.ones((6, 2)), np.ones((4, 1))]
    batch = pl.stage_scenario_batch([p1, p2], y, act, tau=2)
    S, T_b, n_b, P_b = batch.dims
    assert (S, T_b, n_b, P_b) == (2, 8, 2, 4)       # 3->4 windows, P 4
    assert batch.T == [6, 4] and batch.n == [2, 1]
    # phantom rounds/devices are inactive and never aggregate
    assert batch.act[0, 6:].sum() == 0 and batch.act[1, 4:].sum() == 0
    assert batch.act[1, :, 1:].sum() == 0           # phantom device
    assert not batch.is_agg[0, 6:].any()
    assert list(np.nonzero(batch.is_agg[0])[0]) == [1, 3, 5]


# ---------------------------------------------------------------------------
# batched-vs-scan equivalence
# ---------------------------------------------------------------------------


def test_batched_single_matches_scan_bitwise():
    s = _setup()
    h_scan = _scan(s)
    h_bat = _batched([s], s[1], mesh=None, bucket="exact")[0]
    _assert_matches(h_scan, h_bat)


def test_batched_mixed_bucket_matches_scan_bitwise():
    """One bucket holding three different scenarios — smaller n
    (phantom devices), shorter T (phantom rounds + offset tau) and a
    churned schedule — each trained per-point at the bucket's padded
    staging: the batched histories must match (``_assert_matches``:
    exact schedule, accuracies and H; losses to LOSS_RTOL)."""
    P_b = 128                           # bucket P for this data density
    specs = [dict(n=4, T=12, tau=4, seed=0, max_points=P_b),
             dict(n=6, T=12, tau=4, seed=1, max_points=P_b),
             dict(n=6, T=10, tau=4, seed=3, p_exit=0.2, p_entry=0.15,
                  max_points=P_b)]
    setups = [_setup(**s) for s in specs]
    refs = [_scan(s) for s in setups]
    outs = _batched(setups, setups[0][1], mesh=None)
    assert not all(a.all() for a in refs[2]["active"])   # churn is live
    for h_ref, h_bat in zip(refs, outs):
        _assert_matches(h_ref, h_bat)


def test_batched_exact_staging_matches_scan_histories():
    """With each point's own exact P (the default per-point staging)
    the padded loss reductions associate differently, but the
    shape-insensitive history — aggregation schedule, H weights,
    accuracy curves — must still be exact."""
    setups = [_setup(n=4, T=12, tau=4, seed=0),
              _setup(n=6, T=12, tau=4, seed=1)]
    refs = [_scan(s) for s in setups]
    outs = _batched(setups, setups[0][1], mesh=None)
    for h_ref, h_bat in zip(refs, outs):
        assert h_ref["agg_round"] == h_bat["agg_round"]
        assert h_ref["test_acc"] == h_bat["test_acc"]
        np.testing.assert_array_equal(np.stack(h_ref["H_agg"]),
                                      np.stack(h_bat["H_agg"]))
        np.testing.assert_allclose(np.stack(h_bat["device_loss"]),
                                   np.stack(h_ref["device_loss"]),
                                   rtol=1e-5, atol=1e-6)


def test_batched_validates_bucket_homogeneity():
    s1, s2 = _setup(seed=0), _setup(seed=1)
    bad = dataclasses.replace(s2[0], eta=0.9)
    with pytest.raises(ValueError, match="share"):
        F.run_network_aware_batched([s1[0], bad], s1[1],
                                    [s1[2], s2[2]],
                                    streams=[s1[3], s2[3]])
    with pytest.raises(ValueError, match="one entry per scenario"):
        F.run_network_aware_batched([s1[0]], s1[1], [s1[2], s2[2]])


# ---------------------------------------------------------------------------
# sweep layer: buckets, mixed replan modes, compile-count guarantee
# ---------------------------------------------------------------------------


def _tiny_scale():
    from benchmarks.fog import BenchScale

    return BenchScale(n_train=800, n_test=200, T=8, tau=4)


def test_run_scenarios_batched_rows_match_loop():
    """A dynamics-style sweep (static + churn points with MIXED replan
    modes in one bucket) through run_scenarios: the batched rows must
    carry the same accuracy curves as the per-point loop."""
    from benchmarks.fog import make_scenario, run_scenarios, \
        solve_scenario_plans

    scale = _tiny_scale()
    points = [dict(key={"i": 0}),
              dict(key={"i": 1}, p_exit=0.2, p_entry=0.2, replan="oracle",
                   seed=3),
              dict(key={"i": 2}, p_exit=0.2, p_entry=0.2, replan="once",
                   seed=3),
              dict(key={"i": 3}, p_exit=0.2, p_entry=0.2,
                   replan="predict", seed=3)]
    scenarios = [make_scenario(scale, error_model="discard", **pv)
                 for pv in points]
    plans = solve_scenario_plans(scenarios)
    loop = run_scenarios(scenarios, scale, plans=plans, batch=False,
                         engine="scan")
    bat = run_scenarios(scenarios, scale, plans=plans, engine="batched",
                        mesh=None)
    assert all(r["engine"] == "batched" for r in bat)
    for lr, br in zip(loop, bat):
        assert lr["acc_curve"] == br["acc_curve"]
        assert lr["sim_after"] == br["sim_after"]
        assert lr["avg_active"] == br["avg_active"]


def test_nine_point_grid_compiles_at_most_bucket_programs():
    """The program-cache guarantee: a 9-point fig5-shaped grid (3
    network sizes x 3 seeds -> 3 shape buckets) compiles at most
    #buckets batched training programs."""
    from benchmarks.fog import make_scenario, run_scenarios, \
        scenario_bucket_key

    scale = _tiny_scale()
    scenarios = [make_scenario(scale, key={"n": n, "seed": s}, n=n,
                               error_model="discard", seed=s)
                 for n in (3, 5, 9) for s in (0, 1, 2)]
    buckets = {scenario_bucket_key(sc) for sc in scenarios}
    assert len(buckets) == 3
    before = eng.batched_compile_count()
    run_scenarios(scenarios, scale, engine="batched", mesh=None)
    compiled = eng.batched_compile_count() - before
    assert 0 < compiled <= len(buckets), (compiled, len(buckets))
    # a second identical sweep hits the caches: zero new programs
    before = eng.batched_compile_count()
    run_scenarios(scenarios, scale, engine="batched", mesh=None)
    assert eng.batched_compile_count() == before


# ---------------------------------------------------------------------------
# stacked AsyncEvaluator
# ---------------------------------------------------------------------------


def test_submit_stack_matches_scalar_submits():
    import jax

    data = make_image_dataset(n_train=600, n_test=200, seed=0)
    params, apply_fn = eng.make_model("mlp", jax.random.PRNGKey(0))
    p2 = jax.tree_util.tree_map(lambda a: a * 0.5, params)
    stack = jax.tree_util.tree_map(
        lambda a, b: np.stack([np.stack([a, b]), np.stack([b, a])]),
        params, p2)
    ev = eng.AsyncEvaluator(apply_fn, data[2], data[3])
    ev.submit_stack(stack, n_axes=2)
    ev.submit(params)                     # scalar entries still work
    (tl, tl_s), (ta, ta_s) = ev.collect()
    assert tl.shape == ta.shape == (2, 2)
    ref = eng.AsyncEvaluator(apply_fn, data[2], data[3])
    for p in (params, p2, p2, params):
        ref.submit(p)
    losses, accs = ref.collect()
    np.testing.assert_array_equal(tl.reshape(-1), np.asarray(losses))
    np.testing.assert_array_equal(ta.reshape(-1), np.asarray(accs))
    assert tl_s == losses[0] and ta_s == accs[0]


def test_submit_stack_propagates_errors():
    def bad(p, xx):
        raise ValueError("boom")

    x = np.zeros((4, 3), np.float32)
    y = np.zeros(4, np.int32)
    ev = eng.AsyncEvaluator(bad, x, y)
    ev.submit_stack({"w": np.zeros((2, 3), np.float32)})
    with pytest.raises(RuntimeError) as ei:
        ev.collect()
    assert isinstance(ei.value.__cause__, ValueError)


# ---------------------------------------------------------------------------
# multi-device: batched + sharded composition (subprocess, 8 devices)
# ---------------------------------------------------------------------------


def test_batched_sharded_multi_device_equivalence():
    """8 forced host devices: a two-scenario bucket sharded across the
    mesh (scenario axis vmapped inside each shard, psum aggregation
    issued one window early) must match the per-point scan engine
    within the standard sharded tolerances."""
    import json
    import os
    import subprocess
    import sys
    import textwrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = """
        import copy, json
        import numpy as np
        import jax
        assert jax.device_count() == 8, jax.device_count()
        from repro.core import federated as F
        from repro.core import movement as mv
        from repro.core.costs import synthetic_costs
        from repro.core.topology import fully_connected
        from repro.data import pipeline as pl
        from repro.data.synthetic import make_image_dataset

        def setup(n, T, tau, seed=0, p_exit=0.0, p_entry=0.0):
            data = make_image_dataset(n_train=1000, n_test=300, seed=0)
            cfg = F.FedConfig(n=n, T=T, tau=tau, eta=0.05, model="mlp",
                              seed=seed, p_exit=p_exit, p_entry=p_entry)
            rng = np.random.default_rng(seed)
            traces = synthetic_costs(n, T, rng)
            streams = pl.poisson_streams(n, T, data[1], rng=rng)
            plan = mv.greedy_linear(traces, fully_connected(n))
            activity = (F.churn_activity(cfg, rng)
                        if (p_exit or p_entry) else None)
            return cfg, data, plan, streams, activity

        setups = [setup(5, 9, 3, seed=0),
                  setup(10, 9, 3, seed=3, p_exit=0.2, p_entry=0.15)]
        data = setups[0][1]
        outs = F.run_network_aware_batched(
            [s[0] for s in setups], data, [s[2] for s in setups],
            streams=[copy.deepcopy(s[3]) for s in setups],
            activities=[s[4] for s in setups], mesh="auto")
        res = {}
        for i, (s, hb) in enumerate(zip(setups, outs)):
            h = F.run_network_aware(s[0], data, None, None, s[2],
                                    streams=copy.deepcopy(s[3]),
                                    activity=s[4], engine="scan")
            res[str(i)] = {
                "agg_match": h["agg_round"] == hb["agg_round"],
                "acc": float(np.abs(np.array(h["test_acc"])
                                    - np.array(hb["test_acc"])).max()),
                "loss": float(np.abs(np.array(h["test_loss"])
                                     - np.array(hb["test_loss"])).max()),
                "H": float(np.abs(np.stack(h["H_agg"])
                                  - np.stack(hb["H_agg"])).max()),
                "dl": float(np.abs(np.stack(h["device_loss"])
                                   - np.stack(hb["device_loss"])).max()),
            }
        print(json.dumps(res))
    """
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(repo, "src"))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    d = json.loads(r.stdout.strip().splitlines()[-1])
    for tag, gaps in d.items():
        assert gaps["agg_match"], (tag, gaps)
        assert gaps["acc"] <= 1e-2, (tag, gaps)
        assert gaps["loss"] <= 1e-3, (tag, gaps)
        assert gaps["H"] <= 1e-4, (tag, gaps)
        assert gaps["dl"] <= 1e-3, (tag, gaps)


# ---------------------------------------------------------------------------
# ragged staging: in-bucket == alone bitwise, scan equivalence, warning
# ---------------------------------------------------------------------------


def _batched_ragged(setups, data, **kw):
    return _batched(setups, data, mesh=None, staging="ragged", **kw)


def test_ragged_mixed_bucket_matches_alone_bitwise():
    """The ragged bitwise contract: a scenario's per-round rows are
    contiguous and (device, chunk)-ordered, so its per-device reduction
    order — and its bits — are identical whether it shares the bucket
    with other scenarios (phantom rounds/devices, churn) or runs as a
    ragged bucket of one."""
    specs = [dict(n=4, T=12, tau=4, seed=0),
             dict(n=6, T=12, tau=4, seed=1),
             dict(n=6, T=8, tau=4, seed=3, p_exit=0.2, p_entry=0.15)]
    setups = [_setup(**s) for s in specs]
    together = _batched_ragged(setups, setups[0][1])
    for s, h_grp in zip(setups, together):
        h_alone = _batched_ragged([s], setups[0][1])[0]
        _assert_bitwise(h_alone, h_grp)


def test_ragged_matches_scan_histories():
    """Ragged staging reduces each device's samples in stream order
    (chunk-major), so the shape-insensitive history — aggregation
    schedule, H weights, accuracy curves — matches the per-point scan
    exactly and test losses to LOSS_RTOL; per-device losses differ only
    by padded-reduction association."""
    setups = [_setup(n=4, T=12, tau=4, seed=0),
              _setup(n=6, T=12, tau=4, seed=1)]
    refs = [_scan(s) for s in setups]
    outs = _batched_ragged(setups, setups[0][1])
    for h_ref, h_bat in zip(refs, outs):
        _assert_matches(h_ref, h_bat, device_loss_rtol=1e-5,
                        device_loss_atol=1e-6)


def test_ragged_with_faults_matches_alone_bitwise():
    from repro.core import faults as fl

    fs = fl.FaultSchedule(12, 6, 4, [
        fl.FaultEvent(3, "corrupt", 0, float("nan")),
        fl.FaultEvent(5, "crash", 2),
        fl.FaultEvent(7, "drop", 3)])
    setups = [_setup(n=6, T=12, tau=4, seed=0),
              _setup(n=6, T=12, tau=4, seed=1)]
    faults = [fs, None]
    together = _batched_ragged(setups, setups[0][1], faults=faults,
                               guard=True, quorum=0.3)
    for s, f, h_grp in zip(setups, faults, together):
        h_alone = _batched_ragged([s], setups[0][1],
                                  faults=[f] if f is not None else None,
                                  guard=True, quorum=0.3)[0]
        _assert_matches(h_alone, h_grp)
        if f is not None:       # clean points carry no fault history
            assert h_alone["agg_survivors"] == h_grp["agg_survivors"]
            assert h_alone["agg_quorum_ok"] == h_grp["agg_quorum_ok"]


def test_ragged_inflation_warns_once_per_sweep():
    """S2: the ragged warning prices what ragged staging actually
    executes (padded row-slots vs staged chunk rows), and fires once
    per sweep under the reset_padding_warnings contract."""
    y = np.arange(64, dtype=np.int32)
    n = 8
    # round 0 fills every cell (8 rows); later rounds one cell each ->
    # R_b buckets to 8 while only 11 of 32 row slots hold data
    spike = [[np.arange(3) for _ in range(n)]] + \
        [[np.arange(2)] + [np.empty(0, np.int64)] * (n - 1)
         for _ in range(3)]
    act = [np.ones((4, n))]
    pl.reset_padding_warnings()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        pl.stage_scenario_ragged([spike], y, act, tau=2)
        pl.stage_scenario_ragged([spike], y, act, tau=2)
        assert len([w for w in rec
                    if "ragged bucket pads" in str(w.message)]) == 1
    pl.reset_padding_warnings()                 # new sweep: warns again
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        pl.stage_scenario_ragged([spike], y, act, tau=2)
        assert [w for w in rec
                if "ragged bucket pads" in str(w.message)]


def test_staged_cache_hits_on_repeat_sweep():
    """Warm re-staging: a repeat of the same bucket reuses the staged
    device buffers (cache hit) and reproduces the histories bitwise."""
    setups = [_setup(n=4, T=12, tau=4, seed=0),
              _setup(n=6, T=12, tau=4, seed=1)]
    eng.reset_staged_cache()
    first = _batched(setups, setups[0][1], mesh=None)
    stats = eng.staged_cache_stats()
    assert stats["misses"] >= 1
    second = _batched(setups, setups[0][1], mesh=None)
    stats2 = eng.staged_cache_stats()
    assert stats2["hits"] > stats["hits"]
    for h1, h2 in zip(first, second):
        _assert_bitwise(h1, h2)


# ---------------------------------------------------------------------------
# sweep layer: cost-model dispatch
# ---------------------------------------------------------------------------


def test_run_scenarios_records_dispatch_decisions():
    """The default engine="auto" sweep prices every bucket through the
    cost model and stamps each row with the decision; single-point
    buckets short-circuit to the loop path with reason "S=1"."""
    from benchmarks.fog import make_scenario, run_scenarios

    scale = _tiny_scale()
    # 3 same-shape points (one S=3 bucket) + 1 odd size (an S=1 bucket)
    scenarios = [make_scenario(scale, key={"i": i}, n=4,
                               error_model="discard", seed=i)
                 for i in range(3)]
    scenarios.append(make_scenario(scale, key={"i": 3}, n=9,
                                   error_model="discard", seed=0))
    rows = run_scenarios(scenarios, scale, mesh=None)
    assert all("dispatch" in r for r in rows)
    for r in rows[:3]:
        d = r["dispatch"]
        assert d["path"] in ("loop", "batched")
        assert d["reason"] == "cost-model"
        assert set(d["predicted_s"]) == {"loop", "batched-dense",
                                         "batched-ragged"}
        assert r["engine"] == ("batched" if d["path"] == "batched"
                               else r["engine"])
    d1 = rows[3]["dispatch"]
    assert d1["path"] == "loop" and d1["reason"] == "S=1"


def test_run_scenarios_forced_batched_reports_forced_dispatch():
    from benchmarks.fog import make_scenario, run_scenarios

    scale = _tiny_scale()
    scenarios = [make_scenario(scale, key={"i": i}, n=4,
                               error_model="discard", seed=i)
                 for i in range(2)]
    rows = run_scenarios(scenarios, scale, engine="batched", mesh=None)
    for r in rows:
        assert r["engine"] == "batched"
        assert r["dispatch"]["path"] == "batched"
        assert r["dispatch"]["reason"] == "forced"
        # forced batched keeps the historical dense-staging contract
        assert r["dispatch"]["staging"] == "dense"
