"""Benchmark harness — one function per paper table/figure, plus kernel
micro-benches and the dry-run roofline summary.

Each benchmark prints CSV rows ``name,us_per_call,derived`` where
``derived`` is a compact JSON blob of the table's headline numbers, and
writes the full artifact to results/bench_<name>.json.

    PYTHONPATH=src python -m benchmarks.run                 # default scale
    PYTHONPATH=src python -m benchmarks.run --only table3_settings
    PYTHONPATH=src python -m benchmarks.run --quick         # CI scale
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from benchmarks.fog import DEFAULT, FULL, QUICK, dataset, fog_experiment
from repro.launch.compile_cache import enable_compile_cache

RESULTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "results")

_REGISTRY = {}


def bench(fn):
    _REGISTRY[fn.__name__] = fn
    return fn


# XLA compile counter (jax.monitoring backend_compile events): stamped
# into every bench JSON so recompilation regressions — a sweep that
# suddenly compiles per point instead of per bucket — show up in the
# artifact trajectory across PRs. Reads the shared fan-out counter in
# repro.core.monitoring (ONE process-wide registration, also feeding
# the cost-model EMA and the sanitize recompile watchdog) instead of
# registering a second global listener.
_COMPILES = {"last_emit": 0}


def compile_count() -> int:
    """XLA compiles observed so far (0 if jax.monitoring is absent)."""
    from repro.core import monitoring

    return monitoring.compile_events()


def phase_timings() -> dict:
    """Host seconds by phase since the last ``monitoring.reset()``, read
    from the program's spans (``repro.core.monitoring.totals()``)."""
    from repro.core import monitoring

    tot = monitoring.totals()

    def s(*names):
        return sum(tot.get(k, {}).get("seconds", 0.0) for k in names)

    # stage_s:    prep (the host data plane) + train.stage
    #             (staging, fingerprint, uploads)
    # program_s:  train.device (compiled-program dispatch through
    #             block_until_ready)
    # eval_s:     train.eval (the batched path's stacked off-scan eval)
    # train_s:    train.device + train.eval + train.readback
    # tier_agg_s: train.tiers (the hierarchical plane's tier staging)
    return {"stage_s": s("prep", "train.stage"),
            "program_s": s("train.device"),
            "eval_s": s("train.eval"),
            "train_s": s("train.device", "train.eval", "train.readback"),
            "tier_agg_s": s("train.tiers")}


# hierarchical-run provenance: set by benches that build a TierTree /
# tier mesh (``set_tier_meta``); flat benches stamp the keys as None so
# every bench JSON carries the same meta schema
_TIER_META: dict = {"tier_shape": None, "mesh_dims": None}


def set_tier_meta(tier_shape=None, mesh=None) -> None:
    """Record the current bench's tier shape (group counts per level)
    and mesh axis dims for the ``_bench_meta`` stamp; cleared back to
    None at every ``_emit``."""
    _TIER_META["tier_shape"] = (list(map(int, tier_shape))
                                if tier_shape is not None else None)
    if mesh is None:
        _TIER_META["mesh_dims"] = None
    else:
        _TIER_META["mesh_dims"] = {str(k): int(v) for k, v
                                   in dict(mesh.shape).items()}


def _bench_meta() -> dict:
    """Provenance stamp so bench_*.json trajectories are comparable
    across machines: git SHA, jax version, device kind and count, the
    compile counters for recompilation-regression tracking, and the
    tier/mesh shape for hierarchical benches (None on flat benches)."""
    import subprocess

    import jax

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(RESULTS), capture_output=True,
            text=True, timeout=10).stdout.strip() or None
    except Exception:
        sha = None
    dev = jax.devices()[0]
    return {"git_sha": sha, "jax_version": jax.__version__,
            "backend": jax.default_backend(),
            "device_kind": dev.device_kind,
            "device_count": jax.device_count(),
            "tier_shape": _TIER_META["tier_shape"],
            "mesh_dims": _TIER_META["mesh_dims"],
            "compiles_total": compile_count(),
            "compiles_during_bench": compile_count()
            - _COMPILES["last_emit"],
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S")}


def _emit(name: str, seconds: float, derived: dict):
    os.makedirs(RESULTS, exist_ok=True)
    derived = {**derived, "meta": _bench_meta()}
    _COMPILES["last_emit"] = compile_count()
    set_tier_meta()                      # tier stamp is per-bench
    with open(os.path.join(RESULTS, f"bench_{name}.json"), "w") as f:
        json.dump(derived, f, indent=2, default=float)
    compact = json.dumps(derived.get("headline", derived),
                         default=lambda x: round(float(x), 4)
                         if isinstance(x, (int, float, np.floating)) else str(x))
    print(f"{name},{seconds * 1e6:.0f},{compact}", flush=True)


# ---------------------------------------------------------------------------
# Paper tables
# ---------------------------------------------------------------------------


@bench
def table2_accuracy(scale):
    """Centralized vs federated vs network-aware, iid/non-iid, synthetic
    vs testbed costs (paper Table II)."""
    from repro.core import federated as F

    t0 = time.time()
    rows = {}
    data = dataset(scale.n_train, scale.n_test)
    for model in ("mlp", "cnn"):
        cen = F.run_centralized(
            F.FedConfig(model=model, eta=scale.eta, T=scale.T),
            data, steps=scale.T * 10, batch=512)
        rows[f"centralized/{model}"] = cen["test_acc"]
        for iid in (True, False):
            tag = "iid" if iid else "noniid"
            fed = fog_experiment(scale=scale, model=model, iid=iid,
                                 setting="A")
            rows[f"federated/{model}/{tag}"] = fed["acc"]
            for costs in ("synthetic", "testbed"):
                na = fog_experiment(scale=scale, model=model, iid=iid,
                                    costs=costs, setting="B")
                rows[f"network_aware/{model}/{tag}/{costs}"] = na["acc"]
    # paper claim: network-aware within 4pp of federated
    gaps = [rows[f"federated/{m}/{d}"] -
            rows[f"network_aware/{m}/{d}/testbed"]
            for m in ("mlp", "cnn") for d in ("iid", "noniid")]
    derived = {"rows": rows,
               "headline": {"max_gap_pp": 100 * max(gaps),
                            "claim_within_4pp": bool(max(gaps) <= 0.04)}}
    _emit("table2_accuracy", time.time() - t0, derived)


@bench
def table3_settings(scale):
    """Settings A-E: cost decomposition + accuracy (paper Table III)."""
    t0 = time.time()
    rows = {}
    for setting in "ABCDE":
        r = fog_experiment(scale=scale, setting=setting, model="mlp",
                           train=setting in "AB")
        rows[setting] = {"cost": r["cost"], "acc": r.get("acc")}
    unit_A = rows["A"]["cost"]["unit"]
    unit_B = rows["B"]["cost"]["unit"]
    derived = {"rows": rows, "headline": {
        "unit_cost_reduction_A_to_B": 1 - unit_B / unit_A,
        "claim_geq_40pct": bool((1 - unit_B / unit_A) >= 0.40),
        "process_reduction": 1 - rows["B"]["cost"]["process"]
        / max(rows["A"]["cost"]["process"], 1e-9)}}
    _emit("table3_settings", time.time() - t0, derived)


@bench
def table4_error_costs(scale):
    """Discard-cost model comparison: f·D·r vs −f·G vs f/√G under
    settings B and D (paper Table IV)."""
    t0 = time.time()
    rows = {}
    for em in ("discard", "neg_G", "sqrt"):
        for setting in ("B", "D"):
            r = fog_experiment(scale=scale, setting=setting,
                               error_model=em, train=(setting == "B"))
            rows[f"{em}/{setting}"] = {"cost": r["cost"],
                                       "acc": r.get("acc")}
    derived = {"rows": rows, "headline": {
        "negG_processes_most": bool(
            rows["neg_G/B"]["cost"]["processed_frac"]
            >= rows["sqrt/B"]["cost"]["processed_frac"] - 0.05),
        "negG_total_highest": bool(
            rows["neg_G/B"]["cost"]["process"]
            + rows["neg_G/B"]["cost"]["transfer"]
            >= rows["discard/B"]["cost"]["process"]
            + rows["discard/B"]["cost"]["transfer"] - 1e-6)}}
    _emit("table4_error_costs", time.time() - t0, derived)


@bench
def table5_dynamics(scale):
    """Static vs dynamic network, 1% churn (paper Table V)."""
    t0 = time.time()
    stat = fog_experiment(scale=scale, setting="B")
    dyn = fog_experiment(scale=scale, setting="B", p_exit=0.01,
                         p_entry=0.01, seed=1)
    derived = {"static": {k: stat[k] for k in ("acc", "cost")},
               "dynamic": {k: dyn[k] for k in ("acc", "cost")},
               "headline": {
                   "acc_drop_pp": 100 * (stat["acc"] - dyn["acc"]),
                   "unit_cost_delta": dyn["cost"]["unit"]
                   - stat["cost"]["unit"],
                   "avg_active": dyn.get("avg_active")}}
    _emit("table5_dynamics", time.time() - t0, derived)


# ---------------------------------------------------------------------------
# Paper figures
# ---------------------------------------------------------------------------


def _sweep(name, scale, param_values, claim_fn=None, **fixed):
    t0 = time.time()
    rows = []
    for pv in param_values:
        r = fog_experiment(scale=scale, **fixed, **pv)
        rows.append({**pv, "unit": r["cost"]["unit"],
                     "moved_rate": r["cost"]["moved_rate"],
                     "processed_frac": r["cost"]["processed_frac"],
                     "discarded_frac": r["cost"]["discarded_frac"],
                     "acc": r.get("acc"),
                     "sim_after": r.get("sim_after")})
    derived = {"rows": rows}
    if claim_fn:
        derived["headline"] = claim_fn(rows)
    _emit(name, time.time() - t0, derived)


def _scenario_sweep(name, scale, points, claim_fn=None, *, iters=300,
                    **fixed):
    """fig5/fig6-style sweep through the Scenario layer.

    Plans + training use the paper's discard model (Thm-3 greedy, so
    the recorded claims stay comparable to the paper figures); training
    dispatches to the device-sharded engine (eval streamed off the hot
    path by the AsyncEvaluator) whenever more than one device is
    visible. The SAME sweep is then solved under the 1/√G convex model
    with ONE compiled ``solve_convex_batched`` program per (T, n) group
    — each row carries its ``unit_sqrt`` cost from that batched solve.
    """
    import dataclasses as _dc

    from repro.core import movement as mv

    from benchmarks.fog import (make_scenario, run_scenarios,
                                solve_scenario_plans)

    t0 = time.time()
    scenarios = [make_scenario(scale, key=pv, **pv, **fixed,
                               error_model="discard")
                 for pv in points]
    full = run_scenarios(scenarios, scale, iters=iters)
    rows = [{**r, **{k: r["cost"][k] for k in
                     ("unit", "moved_rate", "processed_frac",
                      "discarded_frac")}} for r in full]
    for r in rows:
        r.pop("cost"), r.pop("acc_curve", None), r.pop("sim_before", None)
    # the sweep's convex cost program: all points of a (T, n) group in
    # one vmapped compiled solve
    convex = [_dc.replace(sc, error_model="sqrt") for sc in scenarios]
    for r, sc, plan in zip(rows, convex,
                           solve_scenario_plans(convex, iters=iters)):
        r["unit_sqrt"] = mv.plan_cost(
            plan, sc.traces, sc.D, error_model="sqrt")["unit"]
    derived = {"rows": rows}
    if claim_fn:
        derived["headline"] = claim_fn(rows)
    _emit(name, time.time() - t0, derived)


@bench
def fig5_nodes(scale):
    """Unit cost decreases & non-iid accuracy improves with n (Fig. 5).

    Routed through the Scenario layer: training on the engine dispatch
    (sharded when multi-device), plus the batched convex solve of the
    same sweep (one compiled program per network size)."""
    _scenario_sweep("fig5_nodes", scale,
                    [{"n": n} for n in (5, 10, 20, 30)],
                    iid=False,
                    claim_fn=lambda rows: {
                        "unit_cost_decreasing": bool(
                            rows[-1]["unit"] <= rows[0]["unit"] + 1e-9),
                        "noniid_acc_improves": bool(
                            rows[-1]["acc"] >= rows[0]["acc"] - 0.02),
                        "units": [r["unit"] for r in rows],
                        "accs": [r["acc"] for r in rows]})


@bench
def fig6_connectivity(scale):
    """Connectivity rho sweep on a random graph (Fig. 6).

    All five rho points share (T, n), so the sweep's convex plans are
    ONE compiled ``solve_convex_batched`` program."""
    _scenario_sweep("fig6_connectivity", scale,
                    [{"rho": r} for r in (0.0, 0.25, 0.5, 0.75, 1.0)],
                    topology="random", iid=False,
                    claim_fn=lambda rows: {
                        "unit_cost_decreasing_in_rho": bool(
                            rows[-1]["unit"] <= rows[0]["unit"] + 1e-9),
                        "moved_rate_increasing": bool(
                            rows[-1]["moved_rate"]
                            >= rows[0]["moved_rate"] - 1e-9),
                        "units": [r["unit"] for r in rows]})


@bench
def fig7_aggregation(scale):
    """Aggregation period tau sweep (Fig. 7)."""
    import dataclasses

    t0 = time.time()
    rows = []
    for tau in (2, 5, 10, 20):
        sc = dataclasses.replace(scale, tau=tau)
        r = fog_experiment(scale=sc, iid=False)
        rows.append({"tau": tau, "acc": r["acc"], "unit": r["cost"]["unit"]})
    derived = {"rows": rows, "headline": {
        "acc_small_tau_geq_acc_large_tau": bool(
            rows[0]["acc"] >= rows[-1]["acc"] - 0.02),
        "accs": [r["acc"] for r in rows]}}
    _emit("fig7_aggregation", time.time() - t0, derived)


@bench
def fig8_topologies(scale):
    """Cost components per topology × medium (Fig. 8)."""
    t0 = time.time()
    rows = {}
    for topo in ("social", "hierarchical", "full"):
        for medium in ("lte", "wifi"):
            # lower f_err so discarding is actually in play (paper Fig. 8
            # shows discard-dominated cost mixes)
            r = fog_experiment(scale=scale, topology=topo, medium=medium,
                               f_err=0.45, train=False)
            rows[f"{topo}/{medium}"] = r["cost"]
    derived = {"rows": rows, "headline": {
        # paper: smaller average degree (hierarchical) limits offloading
        "hierarchical_moves_least": bool(
            rows["hierarchical/wifi"]["moved_rate"]
            <= rows["full/wifi"]["moved_rate"] + 1e-9),
        "wifi_discards_more_than_lte": bool(
            rows["social/wifi"]["discarded_frac"]
            >= rows["social/lte"]["discarded_frac"] - 1e-9)}}
    _emit("fig8_topologies", time.time() - t0, derived)


@bench
def fig9_exit(scale):
    """p_exit sweep with p_entry=2% (Fig. 9)."""
    _sweep("fig9_exit", scale,
           [{"p_exit": p, "p_entry": 0.02, "seed": 5}
            for p in (0.0, 0.01, 0.02, 0.05)],
           claim_fn=lambda rows: {
               "acc_declines_with_exit": bool(
                   rows[-1]["acc"] <= rows[0]["acc"] + 0.02),
               "accs": [r["acc"] for r in rows]})


@bench
def fig10_entry(scale):
    """p_entry sweep with p_exit=2% (Fig. 10)."""
    _sweep("fig10_entry", scale,
           [{"p_exit": 0.02, "p_entry": p, "seed": 6}
            for p in (0.0, 0.01, 0.02, 0.05)],
           claim_fn=lambda rows: {
               "acc_improves_with_entry": bool(
                   rows[-1]["acc"] >= rows[0]["acc"] - 0.02),
               "accs": [r["acc"] for r in rows]})


# ---------------------------------------------------------------------------
# Theory + kernels + roofline
# ---------------------------------------------------------------------------


@bench
def thm5_value_of_offloading(scale):
    """Closed form (15) vs simulated greedy savings on scale-free graphs,
    sweeping the cost range C (claim: approximately linear in C)."""
    from repro.core import movement as mv
    from repro.core import theory as th
    from repro.core.costs import synthetic_costs
    from repro.core.topology import scale_free

    t0 = time.time()
    rng = np.random.default_rng(0)
    n, T = 60, 8
    rows = []
    for C in (0.5, 1.0, 2.0, 4.0):
        adj = scale_free(n, 2, rng)
        deg = adj.sum(1)
        hist = {}
        for k in deg:
            hist[int(k)] = hist.get(int(k), 0) + 1.0 / n
        closed = th.theorem5_network_savings(C, hist)
        tr = synthetic_costs(n, T, rng, f_err=1e9)  # no discarding
        tr.c_node[:] *= C
        tr.c_link[:] = 0.0
        D = np.ones((T, n))
        base = mv.plan_cost(mv.no_movement_plan(T, n), tr, D)["total"]
        got = mv.plan_cost(mv.greedy_linear(tr, adj), tr, D)["total"]
        sim = (base - got) / ((T - 1) * n)  # per-point (last round: no move)
        rows.append({"C": C, "closed_form": closed, "simulated": sim})
    ratio = [r["closed_form"] / r["C"] for r in rows]
    derived = {"rows": rows, "headline": {
        "linear_in_C": bool(max(ratio) - min(ratio) < 0.05 * max(ratio)),
        "sim_vs_closed_relerr": max(
            abs(r["simulated"] - r["closed_form"])
            / max(r["closed_form"], 1e-9) for r in rows)}}
    _emit("thm5_value_of_offloading", time.time() - t0, derived)


@bench
def kernels_micro(scale):
    """Kernel micro-bench: XLA reference-path wall times on CPU (the
    Pallas path is validated in interpret mode; TPU timings require real
    hardware — see EXPERIMENTS.md §Perf)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref

    t0 = time.time()
    rng = np.random.default_rng(0)
    out = {}
    q = jnp.asarray(rng.standard_normal((2, 8, 512, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 2, 512, 64)), jnp.float32)
    f = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v))
    f(q, k, k).block_until_ready()
    t = time.time()
    for _ in range(5):
        f(q, k, k).block_until_ready()
    out["attention_ref_us"] = (time.time() - t) / 5 * 1e6

    xdt = jnp.asarray(rng.standard_normal((2, 8, 512, 64)) * .3, jnp.float32)
    a = jnp.asarray(-np.abs(rng.standard_normal((2, 8, 512))) * .3)
    Bm = jnp.asarray(rng.standard_normal((2, 512, 64)) * .3, jnp.float32)
    g = jax.jit(lambda x, a, b, c: ref.ssd_scan_ref(x, a, b, c))
    g(xdt, a, Bm, Bm).block_until_ready()
    t = time.time()
    for _ in range(5):
        g(xdt, a, Bm, Bm).block_until_ready()
    out["ssd_ref_us"] = (time.time() - t) / 5 * 1e6

    n = 512
    cl = jnp.asarray(rng.random((n, n)), jnp.float32)
    cv = jnp.asarray(rng.random(n), jnp.float32)
    adj = jnp.asarray(rng.random((n, n)) < 0.3)
    h = jax.jit(lambda *a: ref.offload_greedy_ref(*a))
    h(cl, cv, cv, cv, adj)[0].block_until_ready()
    t = time.time()
    for _ in range(10):
        h(cl, cv, cv, cv, adj)[0].block_until_ready()
    out["greedy_ref_us"] = (time.time() - t) / 10 * 1e6
    _emit("kernels_micro", time.time() - t0, {"headline": out})


@bench
def solver_scaling(scale):
    """Movement-solver scaling with network size n: Thm-3 greedy (numpy),
    the Pallas Thm-3 kernel (XLA/interpret path), and the convex solver.
    Supports the Thm-6 guidance: greedy + local repair stays tractable
    where interior-point-style solving would not."""
    import jax.numpy as jnp

    from repro.core import movement as mv
    from repro.core.costs import synthetic_costs
    from repro.core.topology import fully_connected
    from repro.kernels import ops

    t0 = time.time()
    rows = []
    for n in (32, 128, 512):
        rng = np.random.default_rng(0)
        T = 8
        tr = synthetic_costs(n, T, rng)
        adj = fully_connected(n)
        t = time.time()
        mv.greedy_linear(tr, adj)
        t_greedy = time.time() - t

        cl = jnp.asarray(tr.c_link[0], jnp.float32)
        cv = jnp.asarray(tr.c_node[0], jnp.float32)
        fe = jnp.asarray(tr.f_err[0], jnp.float32)
        aj = jnp.asarray(adj)
        ops.greedy_decision(cl, cv, cv, fe, aj)[0].block_until_ready()
        t = time.time()
        for _ in range(3):
            ops.greedy_decision(cl, cv, cv, fe, aj)[0].block_until_ready()
        t_kernel = (time.time() - t) / 3

        t_convex = None
        if n <= 128:
            D = np.full((T, n), 20.0)
            t = time.time()
            mv.solve_convex(tr, adj, D, iters=100)
            t_convex = time.time() - t
        rows.append({"n": n, "greedy_s": t_greedy,
                     "kernel_per_round_s": t_kernel, "convex_s": t_convex})
    derived = {"rows": rows, "headline": {
        "greedy_512_s": rows[-1]["greedy_s"],
        "kernel_512_round_us": rows[-1]["kernel_per_round_s"] * 1e6}}
    _emit("solver_scaling", time.time() - t0, derived)


@bench
def engine_throughput(scale):
    """Scan-compiled engine vs the legacy per-round loop (rounds/sec at
    n=10, T=40, mlp) plus movement-solver wall time: batched min-plus
    greedy vs the seed per-round loop and the pure-Python nested-loop
    reference, at n=512, T=50. Writes results/bench_engine.json — the
    first point of the perf trajectory."""
    import jax

    from repro.core import engine as eng
    from repro.core import movement as mv
    from repro.core.costs import synthetic_costs
    from repro.core.topology import fully_connected
    from repro.data import pipeline as pl2

    t0 = time.time()
    n, T, tau, eta, model = 10, 40, 5, 0.1, "mlp"
    x_tr, y_tr, x_te, y_te = dataset(scale.n_train, scale.n_test)
    # paper-scale fog stream density (~2 samples/device/round: 60k over
    # 125 devices x 240 rounds) and a small eval split: the bench
    # measures engine throughput, not eval FLOPs
    x_ev = np.ascontiguousarray(x_te[:256])
    y_ev = np.ascontiguousarray(y_te[:256])
    rng = np.random.default_rng(0)
    traces = synthetic_costs(n, T, rng)
    adj = fully_connected(n)
    streams = pl2.poisson_streams(n, T, y_tr, rng=rng, mean_per_round=2.0)
    plan = mv.greedy_linear(traces, adj)
    processed = pl2.apply_movement(streams, plan, rng)
    max_pts = pl2.pad_size(processed)
    act = np.ones((T, n), bool)
    params, apply_fn = eng.make_model(model, jax.random.PRNGKey(0))

    def run(runner):
        return runner(apply_fn, params, x_tr, y_tr, x_ev, y_ev, processed,
                      act, tau, eta, max_pts)

    run(eng.run_rounds_legacy)            # warm both paths
    h_scan = run(eng.run_rounds_scan)
    legacy_s, scan_s = [], []
    for _ in range(3):
        t = time.time()
        h_legacy = run(eng.run_rounds_legacy)
        legacy_s.append(time.time() - t)
        t = time.time()
        h_scan = run(eng.run_rounds_scan)
        scan_s.append(time.time() - t)
    legacy_s, scan_s = sorted(legacy_s)[1], sorted(scan_s)[1]   # medians
    acc_gap = max(abs(a - b) for a, b in
                  zip(h_legacy["test_acc"], h_scan["test_acc"]))

    n2, T2 = 512, 50
    tr2 = synthetic_costs(n2, T2, np.random.default_rng(1))
    adj2 = fully_connected(n2)
    t = time.time()
    p_scalar = mv.greedy_linear_scalar(tr2, adj2)
    scalar_s = time.time() - t
    t = time.time()
    p_loop = mv.greedy_linear_loop(tr2, adj2)
    loop_s = time.time() - t
    t = time.time()
    p_vec = mv.greedy_linear(tr2, adj2)
    vec_s = time.time() - t
    identical = bool(np.array_equal(p_scalar.s, p_vec.s)
                     and np.array_equal(p_loop.s, p_vec.s)
                     and np.array_equal(p_loop.r, p_vec.r))

    derived = {
        "engine": {"n": n, "T": T, "model": model,
                   "legacy_s": legacy_s, "scan_s": scan_s,
                   "legacy_rounds_per_s": T / legacy_s,
                   "scan_rounds_per_s": T / scan_s,
                   "acc_curve_gap": acc_gap},
        "movement": {"n": n2, "T": T2,
                     "python_nested_loop_s": scalar_s,
                     "seed_per_round_loop_s": loop_s,
                     "vectorized_s": vec_s,
                     "identical_plan": identical},
        "headline": {
            "engine_speedup": legacy_s / scan_s,
            "scan_rounds_per_s": T / scan_s,
            "greedy_speedup_vs_python_loop": scalar_s / vec_s,
            "greedy_speedup_vs_seed_loop": loop_s / vec_s,
            "greedy_identical_plan": identical}}
    _emit("engine", time.time() - t0, derived)


@bench
def movement_scale(scale):
    """Sparse vs dense movement plane at fog scale: Thm-3 greedy +
    capacity repair at n ∈ {256, 512, 1024}. Measures wall time, peak
    traced allocations (numpy registers its buffers with tracemalloc)
    and process ru_maxrss; asserts both paths emit the identical plan.
    Writes results/bench_movement.json — the sparse path must show no
    O(T·n²) share-tensor allocation."""
    import resource
    import tracemalloc

    from repro.core import movement as mv
    from repro.core.costs import synthetic_costs, with_capacity
    from repro.core.topology import make_topology

    t0 = time.time()
    T = 8
    rows = []
    for n in (256, 512, 1024):
        rng = np.random.default_rng(0)
        tr = with_capacity(synthetic_costs(n, T, rng),
                           cap_node=60.0, cap_link=15.0)
        adj = make_topology("random", n, rng, rho=0.3)
        D = rng.poisson(20, (T, n)).astype(float)

        def sparse_path():
            plan = mv.greedy_linear(tr, adj, backend="numpy")
            return mv.repair_capacities(plan, tr, adj, D)

        def dense_path():
            # same vectorized greedy, then the pre-sparse representation:
            # materialized (T, n, n) core + dense-tensor repair — so the
            # comparison isolates the plan representation, not the
            # (PR-1) greedy vectorization
            plan = mv.greedy_linear(tr, adj, backend="numpy")
            plan = mv.MovementPlan(s=plan.s, r=plan.r)
            return mv.repair_capacities_dense(plan, tr, adj, D)

        def measure(fn):
            tracemalloc.start()
            t = time.time()
            plan = fn()
            wall = time.time() - t
            _, peak = tracemalloc.get_traced_memory()
            tracemalloc.stop()
            return plan, wall, peak

        p_sparse, sparse_s, sparse_peak = measure(sparse_path)
        p_dense, dense_s, dense_peak = measure(dense_path)
        identical = bool(mv.plans_equal(p_sparse, p_dense))
        rows.append({"n": n, "T": T, "edges": len(p_sparse.edges),
                     "sparse_s": sparse_s, "dense_s": dense_s,
                     "sparse_peak_bytes": sparse_peak,
                     "dense_peak_bytes": dense_peak,
                     "dense_s_tensor_bytes": T * n * n * 8,
                     "identical_plan": identical})
    big = rows[-1]
    derived = {"rows": rows,
               "ru_maxrss_kb": resource.getrusage(
                   resource.RUSAGE_SELF).ru_maxrss,
               "headline": {
                   "n1024_speedup": big["dense_s"] / big["sparse_s"],
                   "n1024_sparse_s": big["sparse_s"],
                   "n1024_peak_ratio": big["dense_peak_bytes"]
                   / max(big["sparse_peak_bytes"], 1),
                   "sparse_below_dense_tensor": bool(
                       big["sparse_peak_bytes"]
                       < big["dense_s_tensor_bytes"]),
                   "identical_plans": all(r["identical_plan"]
                                          for r in rows)}}
    _emit("movement", time.time() - t0, derived)


@bench
def sparse_scale(scale):
    """Fully sparse O(E) network plane at fog scale (the PR-7
    headline): (a) planning-throughput curve — edge-list churn
    schedule + per-edge costs + sparse Thm-3 greedy + realization +
    sparse window-rate prediction at n ∈ {1024, 10240, 102400}
    (``--max-n`` caps the sweep; CI stops at 10⁴), with the dense
    oracle timed at the overlapping size and the plans asserted
    bitwise-equal and the sparse path ≥5× faster; (b) an n = max-n,
    T = 50 churn scenario trained END-TO-END through the flat-stream
    scan engine with a tracemalloc peak-allocation guard asserting no
    dense (n, n) array was ever materialized (numpy registers its
    buffers with tracemalloc; one bool (n, n) alone is n² bytes).
    Writes results/bench_sparse_scale.json."""
    import resource
    import tracemalloc

    from repro.core import estimator as est
    from repro.core import federated as F
    from repro.core import movement as mv
    from repro.core import topology as topo
    from repro.core.costs import CostTraces, synthetic_edge_costs
    from repro.data import pipeline as pl

    t0 = time.time()
    T_PLAN, DEG = 16, 8
    sizes = [1024, 10_240, 102_400]
    if scale.max_n:
        sizes = [n for n in sizes if n <= scale.max_n] or [scale.max_n]

    def sparse_plan(n, with_mem=False):
        rng = np.random.default_rng(0)
        src, dst = topo.random_sparse_edges(n, DEG, rng)
        sched = topo.churn_schedule_edges(
            n, src, dst, T_PLAN, 0.05, 0.2, np.random.default_rng(7))
        etr = synthetic_edge_costs(n, T_PLAN, src, dst,
                                   np.random.default_rng(1))
        if with_mem:
            tracemalloc.start()
        t = time.time()
        plan = mv.realize_plan(mv.greedy_linear(etr, sched), sched)
        pred = est.predict_schedule(sched)
        wall = time.time() - t
        peak = None
        if with_mem:
            _, peak = tracemalloc.get_traced_memory()
            tracemalloc.stop()
        return plan, pred, wall, peak, (src, dst, etr)

    rows = []
    for n in sizes:
        plan, pred, wall, peak, _ = sparse_plan(n, with_mem=True)
        rows.append({"n": n, "T": T_PLAN, "edges": len(plan.edges),
                     "sparse_s": wall, "sparse_peak_bytes": peak,
                     "dense_tensor_bytes": T_PLAN * n * n * 8,
                     "peak_over_nn": peak / (n * n)})

    # dense oracle at the overlapping size: same support, same costs
    # (per-edge streams scattered onto (T, n, n)), same churn seed —
    # the plans must agree bit for bit
    n0 = sizes[0]
    plan_s, pred_s, sparse_s, _, (src, dst, etr) = sparse_plan(n0)
    A = np.zeros((n0, n0), bool)
    A[src, dst] = True
    c_link = np.zeros((T_PLAN, n0, n0))
    c_link[:, etr.src, etr.indices] = etr.c_link
    tr = CostTraces(c_node=etr.c_node, c_link=c_link, f_err=etr.f_err,
                    cap_node=etr.cap_node,
                    cap_link=np.full((T_PLAN, n0, n0), np.inf))
    sched_d = topo.churn_schedule(A, T_PLAN, 0.05, 0.2,
                                  np.random.default_rng(7))
    t = time.time()
    plan_d = mv.realize_plan(mv.greedy_linear(tr, sched_d), sched_d)
    pred_d = est.predict_schedule(sched_d)
    dense_s = time.time() - t
    identical = bool(mv.plans_equal(plan_s, plan_d))
    pred_match = all(
        np.array_equal(a, b) for t_ in range(T_PLAN)
        for a, b in zip(pred_s.edges_at(t_), pred_d.edges_at(t_)))
    speedup = dense_s / max(sparse_s, 1e-12)
    assert identical, "sparse plan diverged from the dense oracle"
    assert speedup >= 5.0, (
        f"sparse planning only {speedup:.1f}x faster than the dense "
        f"oracle at n={n0} (acceptance floor is 5x)")

    # end-to-end: n = max(sizes), T = 50 churn scenario through the
    # flat-stream scan engine; the peak-alloc guard is the no-dense
    # proof — any (n, n) numpy array would alone exceed the threshold
    n_big, T_tr, tau = sizes[-1], 50, 10
    rng = np.random.default_rng(0)
    x_tr = rng.random((4096, 28, 28)).astype(np.float32)
    y_tr = rng.integers(0, 10, 4096)
    x_te = rng.random((512, 28, 28)).astype(np.float32)
    y_te = rng.integers(0, 10, 512)
    src, dst = topo.random_sparse_edges(n_big, DEG, rng)
    tracemalloc.start()
    t = time.time()
    sched = topo.churn_schedule_edges(
        n_big, src, dst, T_tr, 0.05, 0.2, np.random.default_rng(7))
    etr = synthetic_edge_costs(n_big, T_tr, src, dst,
                               np.random.default_rng(1))
    plan = mv.realize_plan(mv.greedy_linear(etr, sched), sched)
    flat = pl.poisson_streams_flat(n_big, T_tr, y_tr,
                                   rng=np.random.default_rng(3),
                                   mean_per_round=1.0)
    cfg = F.FedConfig(n=n_big, T=T_tr, tau=tau, eta=0.1, model="linear",
                      seed=0)
    hist = F.run_network_aware(cfg, (x_tr, y_tr, x_te, y_te), etr, None,
                               plan, streams=flat, schedule=sched,
                               engine="scan")
    train_s = time.time() - t
    _, train_peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    # no-dense guard: the smallest dense (n, n) array — bool at full
    # scale, float64 at the CI point — must NOT fit under the traced
    # peak. Below ~8k devices the plane's legitimate O(T·E + samples)
    # working set exceeds n² (linear terms dominate tiny quadratics),
    # so the ratio is recorded but not asserted.
    dense_floor = n_big * n_big * (1 if n_big >= 32_768 else 8)
    no_dense = bool(train_peak < dense_floor)
    if n_big >= 8_192:
        assert no_dense, (
            f"end-to-end peak {train_peak} bytes >= {dense_floor} — a "
            f"dense (n={n_big})² array fits under the traced peak")

    derived = {
        "rows": rows,
        "ru_maxrss_kb": resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss,
        "train": {"n": n_big, "T": T_tr, "tau": tau,
                  "samples": int(flat.idx.shape[0]),
                  "train_s": train_s, "train_peak_bytes": train_peak,
                  "nn_bytes": n_big * n_big,
                  "test_acc": hist["test_acc"],
                  "final_acc": hist["test_acc"][-1]},
        "headline": {
            "n_max": sizes[-1],
            "plan_speedup_vs_dense": speedup,
            "plans_identical": identical,
            "predictions_identical": bool(pred_match),
            "train_n": n_big,
            "train_s": train_s,
            "train_peak_over_nn": train_peak / (n_big * n_big),
            "no_dense_nn_materialized": no_dense,
            "final_acc": hist["test_acc"][-1]}}
    _emit("sparse_scale", time.time() - t0, derived)


@bench
def hier_scale(scale):
    """Hierarchical fog aggregation at fog scale (the tier-plane
    headline): a 3-tier TierTree over n = 10⁵ devices (``--max-n``
    caps it; CI runs the 10⁴ point) trains a T = 50 churn scenario
    end-to-end on one host — movement solved strictly WITHIN tier-1
    gateway groups, eq. (4) composed up the tree with per-tier τ — and
    is compared against the flat all-to-server plane at the same τ_0:
    rounds/sec and parameter bytes moved per window. The tracemalloc
    no-(n, n) guard is asserted at EVERY tier's build phase and around
    both trainings, the L=1 bitwise-collapse contract is re-proven
    in-process, and per-tier traffic accounting lands in the JSON with
    cross-tier bytes strictly below the flat plane's all-to-server
    traffic at n ≥ 10⁴. Writes results/bench_hier_scale.json."""
    import resource
    import tracemalloc

    import jax

    from repro.core import engine as eng
    from repro.core import federated as F
    from repro.core import hierarchy as hr
    from repro.core import monitoring
    from repro.core import movement as mv
    from repro.core import topology as topo
    from repro.core.costs import synthetic_edge_costs
    from repro.data import pipeline as pl
    from repro.launch import mesh as mesh_lib

    t0 = time.time()
    n_big = 102_400
    if scale.max_n:
        n_big = min(n_big, scale.max_n)
    T_tr, DEG = 50, 8
    taus = (5, 10, 20)
    g1, g2 = max(2, n_big // 100), max(1, n_big // 3200)
    tree = hr.TierTree.balanced(n_big, (g1, g2, 1), taus)
    tmesh = mesh_lib.tier_mesh_for(tree)
    set_tier_meta(tier_shape=tree.group_counts, mesh=tmesh)

    # the smallest dense (n, n) array — bool at full scale, float64 at
    # the CI point — must never fit under any phase's traced peak (see
    # sparse_scale for the small-n caveat)
    dense_floor = n_big * n_big * (1 if n_big >= 32_768 else 8)
    peaks = {}

    def guarded(tag, fn):
        tracemalloc.start()
        out = fn()
        _, pk = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        peaks[tag] = pk
        if n_big >= 8_192:
            assert pk < dense_floor, (
                f"{tag}: peak {pk} bytes >= {dense_floor} — a dense "
                f"(n={n_big})² array fits under the traced peak")
        return out

    rng = np.random.default_rng(0)
    x_tr = rng.random((4096, 28, 28)).astype(np.float32)
    y_tr = rng.integers(0, 10, 4096)
    x_te = rng.random((512, 28, 28)).astype(np.float32)
    y_te = rng.integers(0, 10, 512)
    data = (x_tr, y_tr, x_te, y_te)
    src, dst = topo.random_sparse_edges(n_big, DEG, rng)

    # tier-1 build plane, each stage under the no-(n, n) guard; the
    # node_offset draws this tier's churn from its own rng stream
    sched = guarded("tier1_schedule", lambda: topo.churn_schedule_edges(
        n_big, src, dst, T_tr, 0.05, 0.2, np.random.default_rng(7),
        tau=taus[0], node_offset=1))
    etr = guarded("tier1_costs", lambda: synthetic_edge_costs(
        n_big, T_tr, src, dst, np.random.default_rng(1)))
    plan_h = guarded("tier1_movement",
                     lambda: hr.solve_tier_movement(tree, etr, sched))
    e = plan_h.edges
    off = e.src != e.dst
    cross = int((tree.parents[0][e.src[off]]
                 != tree.parents[0][e.dst[off]]).sum())
    assert cross == 0, (f"{cross} movement edges cross a gateway "
                        "boundary")
    # upper tiers move parameters, not data: their build product is
    # the ancestor map + group census + traffic row — guard each
    anc = tree.ancestors()
    for lv in range(2, tree.levels + 1):
        guarded(f"tier{lv}_staging",
                lambda lv=lv: np.bincount(
                    anc[lv - 1], minlength=tree.group_counts[lv - 1]))
    params, _ = eng.make_model("linear", jax.random.PRNGKey(0))
    n_params = int(sum(p.size for p in
                       jax.tree_util.tree_leaves(params)))
    traffic = guarded("tier_traffic",
                      lambda: hr.tier_traffic(tree, n_params))
    if n_big >= 10_240:
        assert (traffic["cross_tier_bytes_per_window"]
                < traffic["flat_bytes_per_window"]), traffic

    flat = pl.poisson_streams_flat(n_big, T_tr, y_tr,
                                   rng=np.random.default_rng(3),
                                   mean_per_round=1.0)
    cfg = F.FedConfig(n=n_big, T=T_tr, tau=taus[0], eta=0.1,
                      model="linear", seed=0)

    monitoring.reset()
    t = time.time()
    hist_h = guarded("train_hier", lambda: F.run_network_aware(
        cfg, data, etr, None, plan_h, streams=flat, schedule=sched,
        engine="scan", hierarchy=tree))
    hier_s = time.time() - t
    phases = phase_timings()

    # flat baseline at the same τ_0: full-support movement, all
    # uploads converge on one server every window
    plan_f = guarded("flat_movement", lambda: mv.realize_plan(
        mv.greedy_linear(etr, sched), sched))
    t = time.time()
    hist_f = guarded("train_flat", lambda: F.run_network_aware(
        cfg, data, etr, None, plan_f, streams=flat, schedule=sched,
        engine="scan"))
    flat_s = time.time() - t

    # L=1 collapse contract, re-proven in-process at small n with
    # churn: an L=1 tree's history must be bitwise the flat scan's
    n_s = 64
    src_s, dst_s = topo.random_sparse_edges(n_s, 4, np.random.default_rng(2))
    sched_s = topo.churn_schedule_edges(
        n_s, src_s, dst_s, 20, 0.1, 0.3, np.random.default_rng(7),
        tau=taus[0])
    flat_small = pl.poisson_streams_flat(n_s, 20, y_tr,
                                         rng=np.random.default_rng(3),
                                         mean_per_round=2.0)
    etr_s = synthetic_edge_costs(n_s, 20, src_s, dst_s,
                                 np.random.default_rng(1))
    plan_s = mv.realize_plan(mv.greedy_linear(etr_s, sched_s), sched_s)
    cfg_s = F.FedConfig(n=n_s, T=20, tau=taus[0], eta=0.1,
                        model="linear", seed=0)
    kw = dict(streams=flat_small, schedule=sched_s, engine="scan")
    h1 = F.run_network_aware(cfg_s, data, etr_s, None, plan_s,
                             hierarchy=hr.TierTree.balanced(
                                 n_s, (1,), (taus[0],)), **kw)
    h0 = F.run_network_aware(cfg_s, data, etr_s, None, plan_s, **kw)
    l1_bitwise = all(
        np.array_equal(np.asarray(h1[k]), np.asarray(h0[k]))
        for k in ("device_loss", "test_loss", "test_acc", "H_agg"))
    assert l1_bitwise, "L=1 TierTree diverged from the flat scan"

    peak_all = max(peaks.values())
    derived = {
        "tiers": {"group_counts": list(tree.group_counts),
                  "taus": list(tree.taus),
                  "widest_bucket": tree.widest_bucket,
                  "mesh_axes": {str(k): int(v) for k, v
                                in dict(tmesh.shape).items()}},
        "traffic": traffic,
        "peaks_bytes": peaks,
        "phase_timings": phases,
        "ru_maxrss_kb": resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss,
        "train": {"n": n_big, "T": T_tr,
                  "samples": int(flat.idx.shape[0]),
                  "hier_s": hier_s, "flat_s": flat_s,
                  "acc_hier": hist_h["test_acc"],
                  "acc_flat": hist_f["test_acc"]},
        "headline": {
            "n": n_big,
            "levels": tree.levels,
            "rounds_per_s_hier": T_tr / hier_s,
            "rounds_per_s_flat": T_tr / flat_s,
            "cross_tier_bytes_per_window":
                traffic["cross_tier_bytes_per_window"],
            "flat_window_bytes": traffic["flat_bytes_per_window"],
            "cross_over_flat": traffic["cross_over_flat"],
            "train_peak_over_nn": peak_all / (n_big * n_big),
            "no_dense_nn_materialized": bool(peak_all < dense_floor),
            "l1_collapse_bitwise": bool(l1_bitwise),
            "final_acc_hier": hist_h["test_acc"][-1],
            "final_acc_flat": hist_f["test_acc"][-1]}}
    _emit("hier_scale", time.time() - t0, derived)


@bench
def network_dynamics(scale):
    """Paper §V-E network-dynamics study through the schedule plane:
    accuracy and total resource cost vs churn rate, replanning-on-event
    (schedule-aware Thm-3 greedy — each round's decision uses that
    round's adjacency, so plans never route to exited nodes) vs
    plan-once (static plan realized against the schedule: in-flight
    data over dead links is lost to the discard vector). A link-flap
    pair exercises the event-list schedule the same way, and a
    constant-schedule guard row times the adapter against the raw
    static path — it must be within noise (a constant schedule never
    materializes the O(T·n²) adjacency). Writes
    results/bench_dynamics.json."""
    from repro.core import movement as mv
    from repro.core.costs import synthetic_costs
    from repro.core.schedule import NetworkSchedule
    from repro.core.topology import fully_connected

    from benchmarks.fog import make_scenario, run_scenarios

    t0 = time.time()
    rates = (0.0, 0.02, 0.05, 0.1)
    scenarios = []
    for rate in rates:
        for replan in ((True,) if rate == 0 else (True, False)):
            scenarios.append(make_scenario(
                scale, key={"kind": "churn", "rate": rate,
                            "replan": replan},
                error_model="discard", p_exit=rate, p_entry=rate,
                replan=replan, seed=7))
    for replan in (True, False):
        scenarios.append(make_scenario(
            scale, key={"kind": "flap", "rate": 0.1, "replan": replan},
            error_model="discard", dynamics="flap", p_flap=0.1,
            replan=replan, seed=7))
    full = run_scenarios(scenarios, scale)
    rows = []
    for r, sc in zip(full, scenarios):
        rows.append({**r["cost"], **{k: r.get(k) for k in
                                     ("kind", "rate", "replan", "acc",
                                      "avg_active")},
                     "n_events": (len(sc.schedule.events_in(0, scale.T))
                                  if sc.schedule is not None else 0)})

    # constant-schedule guard: the adapter must cost nothing static
    n2, T2 = 512, 50
    tr2 = synthetic_costs(n2, T2, np.random.default_rng(1))
    adj2 = fully_connected(n2)
    sched2 = NetworkSchedule.constant(adj2, T2)
    mv.greedy_linear(tr2, adj2)                    # touch pages once
    static_s, const_s = [], []
    for _ in range(3):
        t = time.time()
        p_static = mv.greedy_linear(tr2, adj2)
        static_s.append(time.time() - t)
        t = time.time()
        p_const = mv.greedy_linear(tr2, sched2)
        const_s.append(time.time() - t)
    static_s, const_s = sorted(static_s)[1], sorted(const_s)[1]
    identical = bool(mv.plans_equal(p_static, p_const))

    by = {(r["kind"], r["rate"], r["replan"]): r for r in rows}
    churn_pairs = [(by[("churn", c, True)], by[("churn", c, False)])
                   for c in rates[1:]]
    derived = {
        "rows": rows,
        "const_schedule": {"n": n2, "T": T2, "static_s": static_s,
                           "const_s": const_s},
        "headline": {
            "acc_static": by[("churn", 0.0, True)]["acc"],
            "acc_churn10_replan": by[("churn", 0.1, True)]["acc"],
            "acc_churn10_plan_once": by[("churn", 0.1, False)]["acc"],
            # replan picks the per-point minimum over the TRUE candidate
            # set, so its objective can never exceed the realized
            # plan-once objective
            "replan_cost_never_worse": bool(all(
                a["total"] <= b["total"] + 1e-9
                for a, b in churn_pairs)),
            "plan_once_discards_more": bool(all(
                a["discarded_frac"] <= b["discarded_frac"] + 1e-9
                for a, b in churn_pairs)),
            "const_schedule_overhead": const_s / static_s,
            "const_identical_plan": identical}}
    _emit("dynamics", time.time() - t0, derived)


@bench
def network_prediction(scale):
    """Predictive replanning study (ROADMAP "predictive replanning";
    paper setting-C imperfect information generalized to the network):
    accuracy + total resource cost across three planner views of a
    dynamic network — "oracle" (true schedule, replan-on-event),
    "predict" (schedule ESTIMATED from the observed event history via
    window-averaged link-availability / device-activity rates,
    ``estimator.predict_schedule``) and "once" (static base graph) —
    sweeping churn and link-flap rates; at the highest churn/flap
    points a cost-weighted "expected" row rides along (optimistic
    observed support priced by 1/availability,
    ``estimator.expected_cost_traces``) for comparison against the
    threshold predictor. Every plan is realized against
    the TRUE schedule (send-side link losses + receiver-side arrival
    losses), so predictive planning is judged on what actually gets
    delivered. A static-schedule guard row solves the same point under
    all three modes: they must coincide bitwise. Writes
    results/bench_prediction.json."""
    import dataclasses as _dc

    from repro.core import estimator as est
    from repro.core import movement as mv
    from repro.core.schedule import NetworkSchedule

    from benchmarks.fog import make_scenario, run_scenarios, \
        solve_scenario_plans

    t0 = time.time()
    modes = ("oracle", "predict", "once")
    # cost-weighted expected planning (optimistic support, 1/availability
    # link pricing) rides along at the high-dynamics points, where the
    # threshold predictor prunes hardest and the comparison matters
    expected_at = (("churn", 0.1), ("flap", 0.2))
    points = ([("churn", r) for r in (0.02, 0.05, 0.1)]
              + [("flap", r) for r in (0.05, 0.1, 0.2)])
    scenarios = []
    for kind, rate in points:
        dyn = (dict(p_exit=rate, p_entry=rate) if kind == "churn"
               else dict(dynamics="flap", p_flap=rate))
        here = modes + (("expected",) if (kind, rate) in expected_at
                        else ())
        for mode in here:         # same seed → all modes share
            scenarios.append(make_scenario(    # one true schedule
                scale, key={"kind": kind, "rate": rate, "replan": mode},
                error_model="discard", replan=mode, seed=7, **dyn))
    full = run_scenarios(scenarios, scale)
    rows = []
    for r, sc in zip(full, scenarios):
        row = {**{k: r.get(k) for k in ("kind", "rate", "replan", "acc",
                                        "avg_active")}, **r["cost"]}
        if sc.replan == "predict" and sc.schedule is not None:
            row.update(est.schedule_prediction_accuracy(
                est.predict_schedule(sc.schedule), sc.schedule))
        rows.append(row)

    # static-schedule guard: with a constant schedule the three modes
    # must solve to the SAME plan, bit for bit (prediction of a static
    # network is the network; realization is a pass-through)
    base = make_scenario(scale, key={"kind": "static"},
                         error_model="discard", seed=7)
    sched_c = NetworkSchedule.constant(base.adj, scale.T)
    trio = solve_scenario_plans(
        [_dc.replace(base, schedule=sched_c, replan=m) for m in modes])
    static_bitwise = all(mv.plans_equal(trio[0], p) for p in trio[1:])
    rows.append({"kind": "static", "rate": 0.0, "replan": "all",
                 "static_modes_bitwise": static_bitwise,
                 **mv.plan_cost(trio[0], base.traces, base.D)})

    by = {(r["kind"], r["rate"], r["replan"]): r for r in rows}
    o, p, q = (by[("churn", 0.1, m)] for m in modes)
    acc_gap = o["acc"] - q["acc"]
    recovery = ((p["acc"] - q["acc"]) / acc_gap
                if abs(acc_gap) > 1e-9 else None)
    x = by[("churn", 0.1, "expected")]
    derived = {"rows": rows, "headline": {
        "acc_churn10_oracle": o["acc"],
        "acc_churn10_predict": p["acc"],
        "acc_churn10_once": q["acc"],
        "acc_churn10_expected": x["acc"],
        "cost_churn10_expected_vs_predict":
            x["total"] - p["total"],
        "predict_gap_recovery_churn10": recovery,
        "predict_recovers_gap": bool(recovery is not None
                                     and recovery >= 0.2),
        "pred_link_accuracy_churn10": p.get("link_accuracy"),
        # oracle plans on the true candidate set of every round, so its
        # realized objective lower-bounds both other modes point-wise
        "oracle_cost_never_worse": bool(all(
            by[(k, r, "oracle")]["total"] <= by[(k, r, m)]["total"] + 1e-9
            for k, r in points for m in ("predict", "once"))),
        "static_modes_bitwise": static_bitwise}}
    _emit("prediction", time.time() - t0, derived)


@bench
def fault_tolerance(scale):
    """Fault-injection study (ISSUE-6 robustness): accuracy + cost of
    guarded vs. unguarded aggregation under corrupted-update rates,
    quorum-gated sync under heavy upload loss, plus the two exactness
    guarantees of the fault plane — an empty FaultSchedule with the
    guard ON is bitwise-identical to the fault-free program, and a
    checkpointed run interrupted mid-horizon resumes bitwise-equal to
    an uninterrupted one. Writes results/bench_faults.json."""
    import dataclasses
    import tempfile

    from repro.core import faults as fl
    from repro.core import federated as F

    from benchmarks.fog import (dataset, make_scenario, run_scenarios,
                                solve_scenario_plans)

    t0 = time.time()
    # fault statistics need windows: at rate r each of the T/tau
    # aggregations loses ~r·n contributions, and the offloading plan
    # concentrates data (H weight) on the cheap devices — with only 4
    # windows a single hit on a heavy device dominates the curve, so
    # the study runs on a floored horizon
    scale = dataclasses.replace(scale, T=max(scale.T, 60))

    # all arms share streams/costs/topology bitwise with the clean
    # baseline: the fault rng is a separate stream (seed + 7919)
    def mk(arm, **kw):
        return make_scenario(scale, key={"arm": arm},
                             error_model="discard", seed=7, **kw)

    scenarios = [
        mk("clean"),
        mk("corrupt10_guarded", faults="corrupt", fault_rate=0.10),
        mk("corrupt10_unguarded", faults="corrupt", fault_rate=0.10,
           guard=False),
        mk("corrupt30_guarded", faults="corrupt", fault_rate=0.30),
        mk("drop50_q0", faults="drop", fault_rate=0.50),
        mk("drop50_q60", faults="drop", fault_rate=0.50, quorum=0.60),
        mk("mixed10_guarded", faults="mixed", fault_rate=0.10,
           quorum=0.25),
    ]
    plans = solve_scenario_plans(scenarios, iters=300, seed=0)
    full = run_scenarios(scenarios, scale, plans=plans)
    rows = [{"arm": r["arm"], "acc": r["acc"],
             "avg_active": r["avg_active"],
             "cost_total": r["cost"]["total"],
             "fault_summary": r.get("fault_summary"),
             "quorum_skips": r.get("quorum_skips")} for r in full]

    # exactness guarantee 1: guard ON + zero injected faults must trace
    # to the same bits as the historical clean program
    data = dataset(scale.n_train, scale.n_test)
    sc0 = scenarios[0]

    def run0(**kw):
        return F.run_network_aware(sc0.cfg, data, sc0.traces, sc0.adj,
                                   plans[0], streams=sc0.streams,
                                   engine="scan", **kw)

    clean = run0()
    noop = run0(faults=fl.FaultSchedule(scale.T, sc0.cfg.n, scale.tau),
                guard=True, quorum=0.5)
    clean_noop_bitwise = bool(
        clean["test_acc"] == noop["test_acc"]
        and clean["test_loss"] == noop["test_loss"]
        and all(np.array_equal(a, b) for a, b in
                zip(clean["device_loss"], noop["device_loss"]))
        and np.array_equal(np.asarray(clean["H_agg"]),
                           np.asarray(noop["H_agg"])))

    # exactness guarantee 2: interrupt at the mid-horizon window
    # boundary, resume from the checkpoint, reproduce the bits
    with tempfile.TemporaryDirectory() as tmp:
        ck = os.path.join(tmp, "ck.msgpack")
        half = (scale.T // 2 // scale.tau) * scale.tau or scale.tau
        part = run0(checkpoint_path=ck, stop_after=half)
        res = run0(resume=ck)
        resume_bitwise = bool(
            part.get("stopped_at") == half
            and res["test_acc"] == clean["test_acc"]
            and res["test_loss"] == clean["test_loss"]
            and all(np.array_equal(a, b) for a, b in
                    zip(res["device_loss"], clean["device_loss"])))

    by = {r["arm"]: r for r in rows}
    acc_clean = by["clean"]["acc"]
    derived = {"rows": rows, "headline": {
        "acc_clean": acc_clean,
        "acc_guarded_c10": by["corrupt10_guarded"]["acc"],
        "acc_unguarded_c10": by["corrupt10_unguarded"]["acc"],
        "acc_guarded_c30": by["corrupt30_guarded"]["acc"],
        # acceptance: guarded within 2pp of fault-free at a 10%
        # corrupted-update rate, unguarded collapsed to near-random
        "guard_within_2pp": bool(
            by["corrupt10_guarded"]["acc"] >= acc_clean - 0.02),
        "unguarded_near_random": bool(
            by["corrupt10_unguarded"]["acc"] <= 0.2),
        "quorum_skips_q0": by["drop50_q0"]["quorum_skips"],
        "quorum_skips_q60": by["drop50_q60"]["quorum_skips"],
        "clean_noop_bitwise": clean_noop_bitwise,
        "resume_bitwise": resume_bitwise}}
    _emit("faults", time.time() - t0, derived)


def _staged_bitwise_check(scenarios, plans, scale) -> bool:
    """Rerun the per-point loop with every point's pad size pinned to
    its bucket's P (apples-to-apples staging: identical padded shapes)
    and assert the batched path's FULL histories — per-round device
    losses, test losses/accuracies, H weights — are bitwise-identical
    per scenario."""
    import dataclasses as _dc

    from repro.core import federated as F
    from repro.data import pipeline as pl2

    from benchmarks.fog import dataset, scenario_bucket_key

    data = dataset(scale.n_train, scale.n_test)
    groups: dict = {}
    for b, sc in enumerate(scenarios):
        groups.setdefault(scenario_bucket_key(sc), []).append(b)
    ok = True
    for idxs in groups.values():
        # same capped policy as stage_scenario_batch, so the check
        # certifies the staging the timed batched sweep actually ran
        P_b = pl2.bucket_size(max(
            F._prepare_streams(scenarios[b].cfg, data, plans[b],
                               scenarios[b].streams,
                               scenarios[b].activity,
                               scenarios[b].schedule)[3]
            for b in idxs), max_inflation=pl2.BUCKET_MAX_INFLATION)
        cfgs = [_dc.replace(scenarios[b].cfg, max_points=P_b)
                for b in idxs]
        outs = F.run_network_aware_batched(
            cfgs, data, [plans[b] for b in idxs],
            streams=[scenarios[b].streams for b in idxs],
            activities=[scenarios[b].activity for b in idxs],
            schedules=[scenarios[b].schedule for b in idxs], mesh=None)
        for cfg_b, b, hb in zip(cfgs, idxs, outs):
            sc = scenarios[b]
            hl = F.run_network_aware(cfg_b, data, sc.traces, sc.adj,
                                     plans[b], streams=sc.streams,
                                     activity=sc.activity,
                                     schedule=sc.schedule, engine="scan")
            ok &= (hl["agg_round"] == hb["agg_round"]
                   and hl["test_acc"] == hb["test_acc"]
                   and hl["test_loss"] == hb["test_loss"]
                   and np.array_equal(np.stack(hl["device_loss"]),
                                      np.stack(hb["device_loss"]))
                   and np.array_equal(np.stack(hl["H_agg"]),
                                      np.stack(hb["H_agg"])))
    return bool(ok)


def _timed(fn) -> float:
    t = time.time()
    fn()
    return time.time() - t


def _uniq_dispatches(rows) -> list:
    """The distinct per-bucket dispatch decisions of a sweep's rows
    (each bucket's decision is stamped on every one of its rows)."""
    out = []
    for r in rows:
        d = r.get("dispatch")
        if d is not None and d not in out:
            out.append(d)
    return out


def _ragged_alone_check(scenarios, plans, scale) -> bool:
    """Train one representative scenario of every fig5 bucket ALONE
    under ragged staging and assert its FULL history — per-round
    device losses, test losses/accuracies, H weights — is
    bitwise-identical to what it got inside its grouped bucket. This
    is the ragged path's headline guarantee: bucket composition never
    changes a scenario's floats."""
    from repro.core import federated as F

    from benchmarks.fog import dataset, scenario_bucket_key

    data = dataset(scale.n_train, scale.n_test)
    groups: dict = {}
    for b, sc in enumerate(scenarios):
        groups.setdefault(scenario_bucket_key(sc), []).append(b)
    ok = True
    for idxs in groups.values():
        outs = F.run_network_aware_batched(
            [scenarios[b].cfg for b in idxs], data,
            [plans[b] for b in idxs],
            streams=[scenarios[b].streams for b in idxs],
            activities=[scenarios[b].activity for b in idxs],
            schedules=[scenarios[b].schedule for b in idxs],
            mesh=None, staging="ragged")
        b = idxs[0]
        sc = scenarios[b]
        alone = F.run_network_aware_batched(
            [sc.cfg], data, [plans[b]], streams=[sc.streams],
            activities=[sc.activity], schedules=[sc.schedule],
            mesh=None, staging="ragged")[0]
        hb = outs[0]
        ok &= (alone["agg_round"] == hb["agg_round"]
               and alone["test_acc"] == hb["test_acc"]
               and alone["test_loss"] == hb["test_loss"]
               and np.array_equal(np.stack(alone["device_loss"]),
                                  np.stack(hb["device_loss"]))
               and np.array_equal(np.stack(alone["H_agg"]),
                                  np.stack(hb["H_agg"])))
    return bool(ok)


@bench
def scenario_batched(scale):
    """Whole-sweep wall time + compile count: cost-model-DISPATCHED
    sweeps (each shape bucket routed to the per-point loop or to the
    batched engine under dense or ragged staging, whichever the
    ``core.costmodel`` predicts cheapest) vs the forced per-point
    engine-dispatch loop, on fig5-, dynamics- and prediction-shaped
    grids. Both paths get the SAME precomputed plans, so the
    comparison isolates training execution. The dispatched sweep runs
    FIRST each grid, while nothing is compiled, so its "cold" timing
    is the sweep cost a user pays on first shapes; warm timings are
    the min over ``--repeat`` steady-state repeats (the forced loop
    runs in between mark the loop programs compiled, so warm dispatch
    prices the loop path fairly and keeps only buckets where batching
    still wins — the warm staged cache re-uses device
    buckets across repeats). RECORDS (the test suite is what asserts —
    tests/test_engine_batched.py) whether the per-scenario accuracy
    histories are bitwise-equal to the loop path, whether a fig5
    scenario's full ragged history is bitwise-independent of its
    bucket, and the per-phase (solve/stage/program/eval) breakdown of
    the warm dispatched sweep. Writes results/bench_scenarios.json.

    Reading the rows: "dispatch" shows each bucket's routing with the
    model's predicted seconds and compile counts. Grids run
    sequentially in one process, so a later grid's loop timings
    inherit programs earlier grids compiled; the dispatched path's
    cost model sees the same process state, which is exactly what it
    prices."""
    from repro.core import costmodel as cm
    from repro.core import engine as eng
    from repro.core import monitoring

    from benchmarks.fog import (make_scenario, run_scenarios,
                                scenario_bucket_key,
                                solve_scenario_plans)

    t0 = time.time()
    # paper-density fog streams (~4 samples/device/round — the testbed
    # regime whose per-point programs are small enough that compile /
    # dispatch / transfer overheads dominate a sweep, per the ISSUE
    # motivation; density-heavy sweeps shift toward FLOP parity and the
    # batched win compresses to the compile savings)
    density = dict(mean_per_round=4.0)
    grids = {
        # fig5 grid: 3 network sizes x 6 seeds (paper error bars) -> 3
        # buckets; the loop compiles per point (distinct Poisson P per
        # seed), the batched path once per bucket
        "fig5": [dict(n=n, seed=s, iid=False, **density)
                 for n in (5, 10, 20) for s in range(6)],
        # dynamics-shaped: churn rates x replan-on-event vs plan-once
        "dynamics": [dict(p_exit=r, p_entry=r, replan=rp, seed=7,
                          **density)
                     for r in (0.02, 0.1)
                     for rp in ("oracle", "once")],
        # prediction-shaped: three planner views of one churned network
        "prediction": [dict(p_exit=0.05, p_entry=0.05, replan=m, seed=7,
                            **density)
                       for m in ("oracle", "predict", "once")],
    }
    repeats = max(int(getattr(scale, "repeats", 1)), 1)
    rows = []
    for gname, points in grids.items():
        scenarios = [make_scenario(scale, key={"grid": gname, **pv},
                                   error_model="discard", **pv)
                     for pv in points]
        t = time.time()
        plans = solve_scenario_plans(scenarios)
        solve_s = time.time() - t
        n_buckets = len({scenario_bucket_key(sc) for sc in scenarios})

        # dispatched sweep first: truly cold process state for this
        # grid, so the cost model prices compiles for every candidate
        b0 = eng.batched_compile_count()
        c0, t = compile_count(), time.time()
        disp = run_scenarios(scenarios, scale, plans=plans,
                             engine="auto")
        disp_cold_s = time.time() - t
        disp_compiles = compile_count() - c0
        disp_train_programs = eng.batched_compile_count() - b0
        dispatch_cold = _uniq_dispatches(disp)

        c0, t = compile_count(), time.time()
        loop = run_scenarios(scenarios, scale, plans=plans, batch=False,
                             engine="auto")
        loop_cold_s = time.time() - t
        loop_compiles = compile_count() - c0

        loop_warm_s = min(
            _timed(lambda: run_scenarios(scenarios, scale, plans=plans,
                                         batch=False, engine="auto"))
            for _ in range(repeats))
        disp_warm_s, phases, disp_warm = None, None, disp
        for _ in range(repeats):
            monitoring.reset()
            t = time.time()
            out = run_scenarios(scenarios, scale, plans=plans,
                                engine="auto")
            dt = time.time() - t
            if disp_warm_s is None or dt < disp_warm_s:
                disp_warm_s, phases, disp_warm = (
                    dt, phase_timings(), out)
        dispatch_warm = _uniq_dispatches(disp_warm)

        acc_bitwise = all(
            lr["acc_curve"] == br["acc_curve"]
            for lr, br in zip(loop, disp_warm))
        acc_gap = max(
            max((abs(a - b) for a, b in
                 zip(lr["acc_curve"], br["acc_curve"])), default=0.0)
            for lr, br in zip(loop, disp_warm))
        # full histories (losses included) bitwise vs the loop run at
        # the bucket's padded staging — the apples-to-apples identity —
        # and bitwise bucket-independence of the ragged staging
        staged_bitwise = (_staged_bitwise_check(scenarios, plans, scale)
                          if gname == "fig5" else None)
        ragged_alone = (_ragged_alone_check(scenarios, plans, scale)
                        if gname == "fig5" else None)
        rows.append({
            "grid": gname, "points": len(points),
            "buckets": n_buckets,
            "staged_histories_bitwise": staged_bitwise,
            "ragged_alone_bitwise": ragged_alone,
            "dispatch_cold": dispatch_cold,
            "solve_s": solve_s,
            "loop_cold_s": loop_cold_s,
            "dispatched_cold_s": disp_cold_s,
            "loop_warm_s": loop_warm_s,
            "dispatched_warm_s": disp_warm_s,
            "speedup_cold": loop_cold_s / disp_cold_s,
            "speedup_warm": loop_warm_s / disp_warm_s,
            "warm_repeats": repeats,
            "warm_phases": {k: round(v, 4)
                            for k, v in (phases or {}).items()},
            "dispatch_warm": dispatch_warm,
            "loop_compiles": loop_compiles,
            "dispatched_compiles": disp_compiles,
            "dispatched_train_programs": disp_train_programs,
            "train_programs_leq_buckets": bool(
                disp_train_programs <= n_buckets),
            "acc_curves_bitwise": bool(acc_bitwise),
            "acc_curve_gap": acc_gap})
    fig5 = rows[0]
    derived = {"rows": rows, "headline": {
        "fig5_speedup_cold": fig5["speedup_cold"],
        "fig5_speedup_warm": fig5["speedup_warm"],
        "min_grid_speedup_warm": min(r["speedup_warm"] for r in rows),
        "fig5_loop_compiles": fig5["loop_compiles"],
        "fig5_dispatched_compiles": fig5["dispatched_compiles"],
        "fig5_buckets": fig5["buckets"],
        "train_programs_leq_buckets": bool(all(
            r["train_programs_leq_buckets"] for r in rows)),
        "acc_curves_bitwise": bool(all(
            r["acc_curves_bitwise"] for r in rows)),
        "fig5_staged_histories_bitwise": fig5[
            "staged_histories_bitwise"],
        "fig5_ragged_alone_bitwise": fig5["ragged_alone_bitwise"],
        "compile_s_ema": round(cm.MODEL.compile_s, 3)}}
    _emit("scenarios", time.time() - t0, derived)


@bench
def convex_batched(scale):
    """Batched (vmapped) convex movement sweep vs one-solve-per-point:
    same plans from one compiled program."""
    from repro.core import movement as mv
    from repro.core.costs import testbed_like_costs
    from repro.core.topology import make_topology

    from benchmarks.fog import batched_convex_plans, convex_sweep_costs

    t0 = time.time()
    n, T, iters = 10, 12, 300
    rng = np.random.default_rng(0)
    adj = make_topology("full", n, rng)
    scenarios = [(testbed_like_costs(n, T, np.random.default_rng(0),
                                     f_err=f_err, medium=medium),
                  adj, np.full((T, n), 20.0))
                 for f_err in (0.3, 0.7) for medium in ("wifi", "lte")]

    # warm both jit caches so the comparison is program time, not compile
    mv.solve_convex(*scenarios[0], error_model="sqrt", iters=iters)
    batched_convex_plans(scenarios, error_model="sqrt", iters=iters)
    t = time.time()
    seq = [mv.solve_convex(tr, a, D, error_model="sqrt", iters=iters)
           for tr, a, D in scenarios]
    seq_s = time.time() - t
    t = time.time()
    bat = batched_convex_plans(scenarios, error_model="sqrt", iters=iters)
    bat_s = time.time() - t
    gap = max(float(np.abs(p.s - q.s).max()) for p, q in zip(seq, bat))
    rows = convex_sweep_costs(n, T, iters=100)
    derived = {"rows": rows,
               "headline": {"n_scenarios": len(scenarios),
                            "sequential_s": seq_s, "batched_s": bat_s,
                            "speedup": seq_s / bat_s,
                            "max_plan_gap": gap}}
    _emit("convex_batched", time.time() - t0, derived)


@bench
def dryrun_roofline(scale):
    """Summarize the 80-combo dry-run baseline into the roofline table."""
    t0 = time.time()
    path = os.path.join(RESULTS, "dryrun_baseline.jsonl")
    if not os.path.exists(path):
        _emit("dryrun_roofline", time.time() - t0,
              {"headline": {"error": "run repro.launch.dryrun --all first"}})
        return
    rows = [json.loads(l) for l in open(path)]
    ok = [r for r in rows if "error" not in r]
    dom = {}
    for r in ok:
        dom[r["dominant"]] = dom.get(r["dominant"], 0) + 1
    worst = sorted(
        (r for r in ok if r["mesh"] == "16x16" and r["kind"] == "train"),
        key=lambda r: r["useful_flops_ratio"])[:3]
    derived = {"n_pass": len(ok), "n_total": len(rows),
               "dominant_hist": dom,
               "worst_useful_flops": [
                   {"arch": r["arch"], "shape": r["shape"],
                    "ratio": r["useful_flops_ratio"]} for r in worst],
               "headline": {"pass": f"{len(ok)}/{len(rows)}",
                            "dominant_hist": dom}}
    _emit("dryrun_roofline", time.time() - t0, derived)


# ---------------------------------------------------------------------------


def main(argv=None) -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names or glob "
                    "patterns (e.g. 'hier_*,sparse_scale')")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--max-n", type=int, default=0,
                    help="cap the device count of the scale sweeps "
                    "(sparse_scale, hier_scale); 0 = no cap")
    ap.add_argument("--repeat", type=int, default=0,
                    help="extra warm repetitions per timed sweep "
                    "(scenario bench takes the min, for stable warm "
                    "timings); 0 = the scale's default")
    args = ap.parse_args(argv)
    compile_count()   # install the shared compile listener before any jit
    scale = QUICK if args.quick else (FULL if args.full else DEFAULT)
    import dataclasses as _dc
    if args.max_n:
        scale = _dc.replace(scale, max_n=args.max_n)
    if args.repeat:
        scale = _dc.replace(scale, repeats=max(args.repeat, 1))
    if args.only:
        # each comma token is an exact name or a glob (``hier_*``);
        # expansion preserves registry order and de-dups
        import fnmatch
        names = []
        for tok in (s.strip() for s in args.only.split(",")):
            if not tok:
                continue
            hits = fnmatch.filter(_REGISTRY, tok)
            if not hits:
                raise SystemExit(f"unknown benchmark {tok!r} (no exact "
                                 f"or glob match); known: "
                                 f"{sorted(_REGISTRY)}")
            names += [h for h in hits if h not in names]
    else:
        names = list(_REGISTRY)
    print("name,us_per_call,derived")
    for name in names:
        _REGISTRY[name](scale)


if __name__ == "__main__":
    main()
