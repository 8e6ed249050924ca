"""Hierarchical fog aggregation (ISSUE-10): TierTree validation and
staging, aggregate_tier vs the flat aggregate_edges oracle, tier
composition telescoping to eq. (4), the L=1 bitwise-collapse contract
through run_rounds_scan(tree=)/run_network_aware (clean, churn and
fault runs), intra-tier movement boundaries, per-tier schedule
restriction, traffic accounting, and the (pod, data) tier mesh."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from repro.core import engine as eng
from repro.core import faults as fl
from repro.core import federated as F
from repro.core import hierarchy as hr
from repro.core import movement as mv
from repro.core import topology as topo
from repro.core.costs import synthetic_edge_costs
from repro.data import pipeline as pl
from repro.data.synthetic import make_image_dataset

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# TierTree: construction, validation, staging helpers
# ---------------------------------------------------------------------------


def test_balanced_tree_shape_and_spec_roundtrip():
    tree = hr.TierTree.balanced(64, (8, 2, 1), (2, 4, 8))
    assert tree.levels == 3
    assert tree.group_counts == (8, 2, 1)
    assert tree.taus == (2, 4, 8)
    assert tree.widest_bucket == 8
    spec = hr.TierTree.from_spec("8@2,2@4,1@8", 64)
    assert spec.group_counts == tree.group_counts
    assert spec.taus == tree.taus
    assert all(np.array_equal(a, b)
               for a, b in zip(spec.parents, tree.parents))


def test_tier_tree_validation_errors():
    with pytest.raises(ValueError, match="divisibility"):
        hr.TierTree.balanced(16, (4, 1), (2, 3))
    with pytest.raises(ValueError, match="root"):
        hr.TierTree.from_spec("4@2,2@4", 16)
    with pytest.raises(ValueError, match="shape"):
        hr.TierTree(n=8, taus=(2, 4),
                    parents=(np.zeros(7, np.int64), np.zeros(1, np.int64)))
    # group ids must be dense 0..g-1 at every level
    bad = np.array([0, 0, 2, 2, 3, 3, 3, 3])
    with pytest.raises(ValueError, match="dense"):
        hr.TierTree(n=8, taus=(2, 4),
                    parents=(bad, np.zeros(4, np.int64)))
    with pytest.raises(ValueError):
        hr.TierTree.from_spec("definitely-not-a-spec", 8)


def test_level_rounds_and_ancestors():
    tree = hr.TierTree.balanced(8, (4, 2, 1), (2, 4, 8))
    np.testing.assert_array_equal(tree.level_rounds(8),
                                  [0, 1, 0, 2, 0, 1, 0, 3])
    anc = tree.ancestors()
    assert len(anc) == 3
    np.testing.assert_array_equal(anc[0], tree.parents[0])
    np.testing.assert_array_equal(anc[1], tree.parents[1][tree.parents[0]])
    assert np.array_equal(anc[2], np.zeros(8, np.int64))


# ---------------------------------------------------------------------------
# aggregate_tier: per-group flat oracle + telescoping composition
# ---------------------------------------------------------------------------


def _stack_params(m, rng):
    return {"w": rng.standard_normal((m, 4, 3)).astype(np.float32),
            "b": rng.standard_normal((m, 2)).astype(np.float32)}


def test_aggregate_tier_matches_aggregate_edges_per_group():
    rng = np.random.default_rng(0)
    m = 9
    W = _stack_params(m, rng)
    H = rng.integers(0, 6, m).astype(np.float32)
    gids = np.array([0, 0, 0, 1, 1, 2, 2, 2, 2])
    Wg, Hg = eng.aggregate_tier(W, H, gids, 3)
    for g in range(3):
        members = np.nonzero(gids == g)[0]
        ref = eng.aggregate_edges(W, H, members, None)
        for k in W:
            np.testing.assert_array_equal(np.asarray(Wg[k][g]),
                                          np.asarray(ref[k]))
        assert float(Hg[g]) == float(H[members].sum())


def test_aggregate_tier_zero_weight_group_yields_zeros():
    rng = np.random.default_rng(1)
    W = _stack_params(4, rng)
    H = np.array([0.0, 0.0, 3.0, 2.0], np.float32)
    Wg, Hg = eng.aggregate_tier(W, H, np.array([0, 0, 1, 1]), 2)
    assert float(Hg[0]) == 0.0
    for k in W:
        assert not np.asarray(Wg[k][0]).any()


def test_two_stage_composition_matches_manual_aggregate_edges():
    """A 2-tier tree's top model must equal the manual two-stage
    composition: aggregate_edges per gateway group, stack, then
    aggregate_edges over the gateway stack with the group H totals —
    and the total weight must telescope to H.sum()."""
    rng = np.random.default_rng(2)
    m = 8
    W = _stack_params(m, rng)
    H = rng.integers(1, 5, m).astype(np.float32)
    g0 = np.array([0, 0, 0, 0, 1, 1, 1, 1])
    W1, H1 = eng.aggregate_tier(W, H, g0, 2)
    Wt, Ht = eng.aggregate_tier(W1, H1, np.zeros(2, np.int64), 1)

    stacked = {k: np.stack([np.asarray(
        eng.aggregate_edges(W, H, np.nonzero(g0 == g)[0], None)[k])
        for g in range(2)]) for k in W}
    ref = eng.aggregate_edges(stacked, np.asarray(H1),
                              np.array([0, 1]), None)
    for k in W:
        np.testing.assert_array_equal(np.asarray(Wt[k][0]),
                                      np.asarray(ref[k]))
    assert float(Ht[0]) == float(H.sum())


# ---------------------------------------------------------------------------
# engine/federated: L=1 bitwise collapse + hierarchical histories
# ---------------------------------------------------------------------------


def _edge_setup(n=12, T=16, tau=4, churn=True):
    data = make_image_dataset(n_train=1200, n_test=400, seed=0)
    cfg = F.FedConfig(n=n, T=T, tau=tau, eta=0.05, model="mlp", seed=0)
    rng = np.random.default_rng(0)
    src, dst = topo.random_sparse_edges(n, 4, rng)
    if churn:
        sched = topo.churn_schedule_edges(n, src, dst, T, 0.1, 0.3,
                                          np.random.default_rng(7),
                                          tau=tau)
    else:
        from repro.core.schedule import NetworkSchedule
        sched = NetworkSchedule.edgelist(n, T, src, dst)
    etr = synthetic_edge_costs(n, T, src, dst, np.random.default_rng(1))
    streams = pl.poisson_streams_flat(n, T, data[1],
                                      rng=np.random.default_rng(3),
                                      mean_per_round=2.0)
    plan = mv.realize_plan(mv.greedy_linear(etr, sched), sched)
    return cfg, data, etr, plan, streams, sched


def _assert_hist_bitwise(ha, hb):
    assert ha["agg_round"] == hb["agg_round"]
    assert ha["test_acc"] == hb["test_acc"]
    assert ha["test_loss"] == hb["test_loss"]
    for a, b in zip(ha["device_loss"], hb["device_loss"]):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.asarray(ha["H_agg"]),
                                  np.asarray(hb["H_agg"]))


@pytest.mark.parametrize("faulty", [False, True])
def test_l1_tree_collapses_bitwise_to_flat_scan(faulty):
    cfg, data, etr, plan, streams, sched = _edge_setup()
    faults = fl.make_faults("mixed", cfg.T, cfg.n, cfg.tau,
                            rate=0.3, seed=5) if faulty else None
    kw = dict(streams=streams, schedule=sched, engine="scan",
              faults=faults)
    h0 = F.run_network_aware(cfg, data, etr, None, plan, **kw)
    tree = hr.TierTree.balanced(cfg.n, (1,), (cfg.tau,))
    h1 = F.run_network_aware(cfg, data, etr, None, plan,
                             hierarchy=tree, **kw)
    _assert_hist_bitwise(h0, h1)
    assert h1["hierarchy"]["levels"] == 1


def test_matched_tau_two_tier_close_to_flat():
    """With taus = (τ, τ) every aggregation is a top round, so the
    composed tree computes flat eq. (4) reassociated per gateway group:
    histories agree to float tolerance (summation order differs)."""
    cfg, data, etr, plan, streams, sched = _edge_setup(churn=False)
    h0 = F.run_network_aware(cfg, data, etr, None, plan,
                             streams=streams, schedule=sched,
                             engine="scan")
    tree = hr.TierTree.balanced(cfg.n, (3, 1), (cfg.tau, cfg.tau))
    h1 = F.run_network_aware(cfg, data, etr, None, plan,
                             streams=streams, schedule=sched,
                             engine="scan", hierarchy=tree)
    np.testing.assert_array_equal(np.asarray(h0["H_agg"]),
                                  np.asarray(h1["H_agg"]))
    np.testing.assert_allclose(h0["test_loss"], h1["test_loss"],
                               rtol=1e-4, atol=1e-5)


def test_hierarchical_history_cumulative_h_and_tier_rounds():
    """H accumulates across sub-tier windows and resets only at top
    rounds: H_agg at each top round equals every sample processed since
    the previous top round, and the tier_agg_* log lines the aggregating
    level of every window."""
    cfg, data, etr, plan, streams, sched = _edge_setup(n=8, T=16, tau=2,
                                                       churn=False)
    tree = hr.TierTree.balanced(cfg.n, (4, 2, 1), (2, 4, 8))
    hist = F.run_network_aware(cfg, data, etr, None, plan,
                               streams=streams, schedule=sched,
                               engine="scan", hierarchy=tree)
    assert hist["agg_round"] == [7, 15]
    assert hist["tier_agg_round"] == [1, 3, 5, 7, 9, 11, 13, 15]
    assert hist["tier_agg_level"] == [1, 2, 1, 3, 1, 2, 1, 3]
    flat = F.run_network_aware(cfg, data, etr, None, plan,
                               streams=streams, schedule=sched,
                               engine="scan")
    Hf = np.asarray(flat["H_agg"])          # (8, n): one row per window
    Hh = np.asarray(hist["H_agg"])          # (2, n): top rounds only
    np.testing.assert_allclose(Hh[0], Hf[:4].sum(0))
    np.testing.assert_allclose(Hh[1], Hf[4:].sum(0))
    assert hist["hierarchy"] == {"levels": 3, "group_counts": [4, 2, 1],
                                 "taus": [2, 4, 8]}


def test_hierarchy_wiring_validation():
    cfg, data, etr, plan, streams, sched = _edge_setup(n=8, T=8, tau=2,
                                                       churn=False)
    tree = hr.TierTree.balanced(8, (2, 1), (2, 4))
    with pytest.raises(ValueError, match="engine"):
        F.run_network_aware(cfg, data, etr, None, plan, streams=streams,
                            schedule=sched, engine="sharded",
                            hierarchy=tree)
    with pytest.raises(ValueError):
        F.run_network_aware(cfg, data, etr, None, plan, streams=streams,
                            schedule=sched, engine="hierarchical")
    with pytest.raises(ValueError, match="checkpoint"):
        F.run_network_aware(cfg, data, etr, None, plan, streams=streams,
                            schedule=sched, hierarchy=tree, stop_after=4)
    bad_tau = hr.TierTree.balanced(8, (2, 1), (4, 8))
    with pytest.raises(ValueError, match="tau"):
        F.run_network_aware(cfg, data, etr, None, plan, streams=streams,
                            schedule=sched, engine="scan",
                            hierarchy=bad_tau)
    bad_n = hr.TierTree.balanced(6, (2, 1), (2, 4))
    with pytest.raises(ValueError, match="n"):
        F.run_network_aware(cfg, data, etr, None, plan, streams=streams,
                            schedule=sched, engine="scan",
                            hierarchy=bad_n)


# ---------------------------------------------------------------------------
# intra-tier movement + schedule restriction + traffic
# ---------------------------------------------------------------------------


def test_restrict_schedule_keeps_only_intra_tier_edges():
    n, T = 16, 10
    tree = hr.TierTree.balanced(n, (4, 1), (2, 4))
    rng = np.random.default_rng(0)
    src, dst = topo.random_sparse_edges(n, 4, rng)
    sched = topo.churn_schedule_edges(n, src, dst, T, 0.1, 0.3,
                                      np.random.default_rng(7), tau=2)
    sub = hr.restrict_schedule(tree, sched)
    g = tree.parents[0]
    np.testing.assert_array_equal(hr.intra_tier_edges(tree, src, dst),
                                  g[src] == g[dst])
    for t in range(T):
        fs, fd = sched.edges_at(t)
        keep = g[fs] == g[fd]
        ss, sd = sub.edges_at(t)
        flat_kept = set(zip(fs[keep].tolist(), fd[keep].tolist()))
        assert set(zip(ss.tolist(), sd.tolist())) == flat_kept
    np.testing.assert_array_equal(sub.activity(), sched.activity())


def test_solve_tier_movement_never_crosses_gateway_boundary():
    n, T = 24, 8
    tree = hr.TierTree.balanced(n, (6, 1), (2, 4))
    rng = np.random.default_rng(0)
    src, dst = topo.random_sparse_edges(n, 5, rng)
    sched = topo.churn_schedule_edges(n, src, dst, T, 0.1, 0.3,
                                      np.random.default_rng(7), tau=2)
    etr = synthetic_edge_costs(n, T, src, dst, np.random.default_rng(1))
    plan = hr.solve_tier_movement(tree, etr, sched)
    e = plan.edges
    moved = e.src != e.dst
    g = tree.parents[0]
    assert np.array_equal(g[e.src[moved]], g[e.dst[moved]])
    # capacity repair stays within the tier too
    plan_d = hr.solve_tier_movement(tree, etr, sched,
                                    D=np.full((T, n), 2.0))
    e = plan_d.edges
    moved = e.src != e.dst
    assert np.array_equal(g[e.src[moved]], g[e.dst[moved]])


def test_restrict_traces_slices_csr_to_intra_tier_columns():
    n, T = 12, 6
    tree = hr.TierTree.balanced(n, (3, 1), (2, 4))
    rng = np.random.default_rng(0)
    src, dst = topo.random_sparse_edges(n, 4, rng)
    etr = synthetic_edge_costs(n, T, src, dst, np.random.default_rng(1))
    sub = hr.restrict_traces(tree, etr)
    g = tree.parents[0]
    assert np.array_equal(g[sub.src], g[sub.indices])
    keep = g[etr.src] == g[etr.indices]
    np.testing.assert_array_equal(sub.c_link, etr.c_link[:, keep])
    np.testing.assert_array_equal(sub.c_node, etr.c_node)


def test_tier_traffic_scales_with_gateways_not_devices():
    tree = hr.TierTree.balanced(10_240, (128, 8, 1), (5, 10, 20))
    tr = hr.tier_traffic(tree, 7850)
    assert tr["flat_bytes_per_window"] == 2 * 10_240 * 7850 * 4
    # cross-tier traffic: 128 gateways every 2nd window + 8 pods every
    # 4th — orders of magnitude under n uploads per window
    assert tr["cross_tier_bytes_per_window"] < tr["flat_bytes_per_window"]
    assert tr["cross_over_flat"] < 0.05
    per = [row["bytes_per_window"] for row in tr["per_tier"]]
    assert len(per) == 3 and per[0] > per[1] > per[2]


# ---------------------------------------------------------------------------
# tier mesh (forced 8-device subprocess)
# ---------------------------------------------------------------------------


def test_tier_mesh_for_pod_data_axes_eight_devices():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    code = """
        import json
        from repro.core import hierarchy as hr
        from repro.launch import mesh as mesh_lib

        out = {}
        m = mesh_lib.tier_mesh_for(hr.TierTree.balanced(64, (4, 1), (2, 4)))
        out["two_d"] = {str(k): int(v) for k, v in dict(m.shape).items()}
        m1 = mesh_lib.tier_mesh_for(hr.TierTree.balanced(64, (1,), (2,)))
        out["flat"] = {str(k): int(v) for k, v in dict(m1.shape).items()}
        print(json.dumps(out))
    """
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    import json
    out = json.loads(r.stdout.strip().splitlines()[-1])
    # 4 gateway pods x 2 data shards; never wider than the widest bucket
    assert out["two_d"] == {"pod": 4, "data": 2}
    assert out["flat"] == {"data": 8}


def test_tier_mesh_single_device_falls_back_to_data_mesh():
    from repro.launch import mesh as mesh_lib
    tree = hr.TierTree.balanced(16, (4, 1), (2, 4))
    m = mesh_lib.tier_mesh_for(tree)
    axes = dict(m.shape)
    if jax.device_count() == 1:
        assert axes == {"data": 1}
    assert int(np.prod(list(axes.values()))) <= jax.device_count()


# ---------------------------------------------------------------------------
# sweep routing: Scenario(hierarchy=) / make_scenario(tiers=)
# ---------------------------------------------------------------------------


def test_run_scenarios_routes_tiered_points_hierarchically():
    """A tiers= sweep point trains through the hierarchical engine
    (never the batched bucket path) and an L=1 spec reproduces its flat
    twin's curves exactly; flat points in the same sweep are
    untouched."""
    from benchmarks.fog import BenchScale, make_scenario, run_scenarios

    scale = BenchScale(n_train=800, n_test=200, T=8, tau=4)
    base = dict(n=8, p_exit=0.1, p_entry=0.2, seed=3)
    scenarios = [make_scenario(scale, key={"i": 0}, **base),
                 make_scenario(scale, key={"i": 1}, tiers="1@4", **base),
                 make_scenario(scale, key={"i": 2}, tiers="4@4,1@8",
                               **base)]
    assert scenarios[1].hierarchy.levels == 1
    assert scenarios[2].hierarchy.group_counts == (4, 1)
    rows = run_scenarios(scenarios, scale, batch=False, engine="scan")
    assert rows[0]["engine"] == "scan"
    assert rows[1]["engine"] == "hierarchical"
    assert rows[2]["engine"] == "hierarchical"
    assert rows[1]["acc_curve"] == rows[0]["acc_curve"]
