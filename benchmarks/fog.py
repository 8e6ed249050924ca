"""Shared harness for the paper-reproduction benchmarks: one fog
experiment = (costs, topology, plan, federated run) -> accuracy + cost
decomposition. Sizes default below paper scale to stay CPU-friendly;
--full restores n_train=60k, T=100."""
from __future__ import annotations

import dataclasses
import functools
import time

import numpy as np

from repro.core import estimator as est
from repro.core import faults as fl
from repro.core import federated as F
from repro.core import movement as mv
from repro.core.costs import (synthetic_costs, testbed_like_costs,
                              with_capacity)
from repro.core.schedule import NetworkSchedule
from repro.core.topology import (churn_schedule, link_flap_schedule,
                                 make_topology)
from repro.data import pipeline as pl
from repro.data.synthetic import make_image_dataset


@dataclasses.dataclass
class BenchScale:
    n_train: int = 20_000
    n_test: int = 4_000
    T: int = 40
    tau: int = 5
    eta: float = 0.1
    repeats: int = 1
    # cap on the device count the scale benches sweep to (0 = no cap);
    # CI sets --max-n so sparse_scale stops at its n=10⁴ point
    max_n: int = 0


QUICK = BenchScale(n_train=8_000, n_test=2_000, T=20, tau=5)
DEFAULT = BenchScale()
FULL = BenchScale(n_train=60_000, n_test=10_000, T=100, tau=10, repeats=3)


@functools.lru_cache(maxsize=2)
def dataset(n_train: int, n_test: int, seed: int = 0):
    return make_image_dataset(n_train=n_train, n_test=n_test, seed=seed)


def make_plan(setting: str, traces, adj, D, error_model="discard",
              gamma=1.0):
    T_, n = D.shape
    if setting == "A":
        return mv.no_movement_plan(T_, n)
    tr, D_plan = traces, D
    if setting in ("C", "E"):
        tr = est.estimate_traces(traces)
        D_plan = est.estimate_counts(D)
    if error_model == "discard":
        plan = mv.greedy_linear(tr, adj)
    else:
        plan = mv.solve_convex(tr, adj, D_plan, error_model=error_model,
                               gamma=gamma, iters=400)
    if setting in ("D", "E"):
        # Table III: plan on estimates, EXECUTE on truth — the repair
        # enforces capacities against the true arrivals (and true
        # traces), exactly like launch.train.solve_setting; repairing
        # against estimated counts under-caps the rounds the estimator
        # under-predicts
        plan = mv.repair_capacities(plan, traces, adj, D)
    return plan


def batched_convex_plans(scenarios, *, error_model="sqrt", gamma=1.0,
                         iters=400, seed=0):
    """Solve a sweep of (traces, adj, D) scenarios in ONE vmapped
    compiled program (all scenarios must share (T, n)) — the batched
    path for cost/topology sweeps that previously re-ran the convex
    solver once per point."""
    traces, adjs, Ds = zip(*scenarios)
    return mv.solve_convex_batched(list(traces), list(adjs), list(Ds),
                                   error_model=error_model, gamma=gamma,
                                   iters=iters, seeds=seed)


def convex_sweep_costs(n, T, *, f_errs=(0.3, 0.7), media=("wifi", "lte"),
                       error_model="sqrt", iters=400, seed=0):
    """Cost sweep (error weight × medium) solved as one batched program.

    Returns rows of {f_err, medium, cost decomposition} — the batched
    counterpart of looping ``fog_experiment`` over cost settings."""
    rng = np.random.default_rng(seed)
    adj = make_topology("full", n, rng)
    scenarios, keys = [], []
    for f_err in f_errs:
        for medium in media:
            tr = testbed_like_costs(n, T, np.random.default_rng(seed),
                                    f_err=f_err, medium=medium)
            D = np.full((T, n), 20.0)
            scenarios.append((tr, adj, D))
            keys.append({"f_err": f_err, "medium": medium})
    plans = batched_convex_plans(scenarios, error_model=error_model,
                                 iters=iters, seed=seed)
    rows = []
    for key, plan, (tr, _, D) in zip(keys, plans, scenarios):
        rows.append({**key, **mv.plan_cost(plan, tr, D,
                                           error_model=error_model)})
    return rows


# ---------------------------------------------------------------------------
# Scenario sweep layer: batched plan solving + engine-dispatched training
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Scenario:
    """One sweep point: costs, topology, data streams and plan recipe.

    The point of the layer is BATCHING: ``solve_scenario_plans`` groups
    scenarios by (T, n, error_model, γ) and solves each convex group in
    ONE vmapped compiled program (``solve_convex_batched``), and
    ``run_scenarios`` trains every point through the engine dispatch —
    the device-sharded scan engine (eval streamed off the hot path)
    when more than one device is visible.
    """

    key: dict
    cfg: "F.FedConfig"
    traces: object
    adj: np.ndarray
    D: np.ndarray
    streams: "pl.FogStreams"
    setting: str = "B"
    error_model: str = "sqrt"
    gamma: float = 1.0
    activity: np.ndarray | None = None
    schedule: NetworkSchedule | None = None
    # "oracle" plans on the true schedule, "predict" on the estimated
    # schedule (estimator.predict_schedule), "expected" on the observed
    # support with 1/availability link pricing (expected_cost_traces),
    # "once" on the static base graph; True/False are legacy aliases
    # for oracle/once. Non-oracle plans are realized against the true
    # schedule.
    replan: bool | str = "oracle"
    # unannounced failures (core.faults.FaultSchedule): never visible
    # to the planner — crash outages only enter at realization, and
    # upload faults only inside the engine's guarded aggregation
    faults: "fl.FaultSchedule | None" = None
    guard: bool = True
    quorum: float = 0.0
    # optional core.hierarchy.TierTree: aggregation composes up the
    # tier tree on the scan substrate; hierarchical points train
    # through the per-point loop (never a batched bucket)
    hierarchy: object | None = None


def make_scenario(scale: BenchScale, *, key=None, n=10, model="mlp",
                  iid=True, costs="testbed", topology="full", rho=1.0,
                  setting="B", error_model="sqrt", gamma=1.0,
                  medium="wifi", p_exit=0.0, p_entry=0.0, f_err=0.7,
                  dynamics=None, p_flap=0.05, p_recover=0.5,
                  replan="oracle", mean_per_round=None, faults=None,
                  fault_rate=0.0, guard=True, quorum=0.0,
                  corrupt_mode="nan", tiers=None, seed=0) -> Scenario:
    """Build one sweep point (same setup recipe as ``fog_experiment``).

    ``dynamics``: None (auto: "churn" when p_exit/p_entry set, else
    static), "churn" (node entry/exit via the ChurnProcess-produced
    NetworkSchedule — the movement plane sees inactive endpoints), or
    "flap" (seeded link up/down events). ``replan``: "oracle" plans on
    the true schedule (replan-on-event), "predict" on the schedule
    ESTIMATED from the observed history (window-averaged availability,
    ``estimator.predict_schedule``), "once" on the static base graph;
    predictive and plan-once plans are then realized against the true
    schedule — in-flight data over dead links or toward churned-out
    receivers is lost (``mv.realize_plan``). ``mean_per_round``
    overrides the Poisson arrival density (default |D|/(nT); the
    paper's fog testbed runs at ~2 samples/device/round).

    ``faults``/``fault_rate`` inject unannounced failures
    (``core.faults.make_faults``: "straggle", "drop", "crash",
    "corrupt" or "mixed" at ``fault_rate``) sampled from a SEPARATE
    rng stream (seed + 7919), so a faulted sweep point shares streams,
    costs and topology bitwise with its fault-free twin. ``guard``/
    ``quorum``/``corrupt_mode`` configure the engine-side tolerance.

    ``tiers`` — hierarchical aggregation: a ``core.hierarchy.TierTree``
    or a CLI spec string (``"4@10,1@20"``; the first period must equal
    ``scale.tau``). Hierarchical points always train through the
    per-point loop (the batched bucket engine has no tier program).
    """
    rng = np.random.default_rng(seed)
    data = dataset(scale.n_train, scale.n_test)
    cfg = F.FedConfig(n=n, T=scale.T, tau=scale.tau, eta=scale.eta,
                      model=model, iid=iid, seed=seed,
                      p_exit=p_exit, p_entry=p_entry)
    if costs == "testbed":
        traces = testbed_like_costs(n, scale.T, rng, f_err=f_err,
                                    medium=medium)
    else:
        traces = synthetic_costs(n, scale.T, rng, f_err=f_err)
    adj = make_topology(topology, n, rng, rho=rho,
                        costs=traces.c_node.mean(0))
    streams = pl.poisson_streams(n, scale.T, data[1], iid=iid, rng=rng,
                                 mean_per_round=mean_per_round)
    D = pl.counts(streams)
    if setting in ("D", "E"):
        traces = with_capacity(traces, float(D.mean()))
    if dynamics is None:
        dynamics = "churn" if (p_exit or p_entry) else "static"
    schedule = None
    if dynamics == "churn" and (p_exit or p_entry):
        # same rng position/stepping as the legacy churn_activity call;
        # the engine mask derives from the schedule (single source of
        # truth), so Scenario.activity stays None
        schedule = churn_schedule(adj, scale.T, p_exit, p_entry, rng,
                                  tau=scale.tau)
    elif dynamics == "flap":
        schedule = link_flap_schedule(adj, scale.T, rng, p_down=p_flap,
                                      p_up=p_recover)
    fault_sched = faults if isinstance(faults, fl.FaultSchedule) else \
        fl.make_faults(faults, scale.T, n, scale.tau, rate=fault_rate,
                       seed=seed + 7919, corrupt=corrupt_mode)
    hierarchy = tiers
    if isinstance(tiers, str):
        from repro.core import hierarchy as hr
        hierarchy = hr.TierTree.from_spec(tiers, n)
    return Scenario(key=dict(key or {}), cfg=cfg, traces=traces, adj=adj,
                    D=D, streams=streams, setting=setting,
                    error_model=error_model, gamma=gamma,
                    schedule=schedule, replan=replan, faults=fault_sched,
                    guard=guard, quorum=quorum, hierarchy=hierarchy)


def _estimated(sc: Scenario):
    """Imperfect-information settings plan on estimated traces/counts.

    ``replan="expected"`` additionally reprices the planner's link
    costs by 1/availability (``est.expected_cost_traces``) — the
    cost-weighted half of expected planning; the support half lives in
    ``_plan_network``."""
    if sc.setting in ("C", "E"):
        tr, D = (est.estimate_traces(sc.traces),
                 est.estimate_counts(sc.D))
    else:
        tr, D = sc.traces, sc.D
    if sc.schedule is not None and replan_mode(sc.replan) == "expected":
        tr = est.expected_cost_traces(tr, sc.schedule)
    return tr, D


def replan_mode(replan) -> str:
    """Normalize ``Scenario.replan``: "oracle" / "predict" /
    "expected" / "once", with the legacy booleans as aliases
    (True → oracle, False → once)."""
    if replan is True:
        return "oracle"
    if replan is False:
        return "once"
    if replan in ("oracle", "predict", "expected", "once"):
        return replan
    raise ValueError(f"unknown replan mode {replan!r}; expected "
                     "'oracle', 'predict', 'expected', 'once' or a bool")


def _plan_network(sc: Scenario):
    """What the planner sees: the true schedule (oracle replanning),
    the schedule PREDICTED from the observed history (setting-C style
    imperfect network information; "expected" keeps the optimistic
    observed support and pairs it with 1/availability link pricing in
    ``_estimated``), or the static base graph (plan-once)."""
    if sc.schedule is None:
        return sc.adj
    mode = replan_mode(sc.replan)
    if mode == "oracle":
        return sc.schedule
    if mode in ("predict", "expected"):
        return est.predict_schedule(
            sc.schedule, mode="threshold" if mode == "predict"
            else "expected")
    return sc.adj


def solve_scenario_plans(scenarios: list[Scenario], *, iters=400,
                         seed=0) -> list[mv.MovementPlan]:
    """Plans for a whole sweep, convex solves batched per group.

    Scenarios sharing (T, n, error_model, γ) are stacked into ONE
    ``solve_convex_batched`` call — one compiled program per group (a
    sweep over a single network size is exactly one program). Greedy
    (discard-cost) scenarios emit sparse plans per point; capacity
    settings (D/E) get the streamed sparse repair afterwards.

    Dynamics: points carrying a :class:`NetworkSchedule` plan against
    the network view their ``replan`` mode allows — the true schedule
    ("oracle"), the estimated schedule ("predict"), or the static base
    graph ("once") — and EVERY scheduled plan is then realized against
    the true schedule: in-flight data over missing links, or toward
    receivers that churn out by the arrival round, is lost to the
    discard vector (``mv.realize_plan``). Oracle GREEDY plans pass
    through realization unchanged (``greedy_linear`` is
    receiver-aware); oracle convex plans may shed receiver-side shares
    — the convex solver prices per-round adjacency only, and
    realization is what keeps every mode's accounting on the network
    that actually happened.
    """
    plans: list = [None] * len(scenarios)
    nets = [_plan_network(sc) for sc in scenarios]
    groups: dict[tuple, list[int]] = {}
    for b, sc in enumerate(scenarios):
        T_, n = sc.D.shape
        if sc.setting == "A":
            plans[b] = mv.no_movement_plan(T_, n)
        elif sc.error_model == "discard":
            tr, _ = _estimated(sc)
            plans[b] = mv.greedy_linear(tr, nets[b])
        else:
            groups.setdefault((T_, n, sc.error_model, sc.gamma),
                              []).append(b)
    for (_, _, em, gamma), idxs in groups.items():
        estimated = [_estimated(scenarios[b]) for b in idxs]
        trs = [tr for tr, _ in estimated]
        Ds = [D for _, D in estimated]
        adjs = [nets[b] for b in idxs]
        for b, p in zip(idxs, mv.solve_convex_batched(
                trs, adjs, Ds, error_model=em, gamma=gamma, iters=iters,
                seeds=seed)):
            plans[b] = p
    for b, sc in enumerate(scenarios):
        if sc.setting in ("D", "E"):
            # Table III: plan on estimates, execute on truth — repair
            # enforces capacities against the TRUE arrivals (parity
            # with make_plan and launch.train.solve_setting)
            plans[b] = mv.repair_capacities(plans[b], sc.traces,
                                            nets[b], sc.D)
        if sc.faults is not None and sc.faults.has_crashes:
            # the EXECUTED network also loses crashed nodes the planner
            # never saw: in-transit shares toward a crashed receiver
            # die through the same receiver-side machinery as churn
            plans[b] = mv.realize_plan(
                plans[b], sc.faults.compose(sc.schedule, adj=sc.adj))
        elif sc.schedule is not None:
            plans[b] = mv.realize_plan(plans[b], sc.schedule)
    return plans


def scenario_bucket_key(sc: Scenario, *, bucket: str = "pow2") -> tuple:
    """The shape bucket a sweep point trains in: scenarios sharing this
    key run through ONE compiled program of the batched engine (the
    per-point sample budget P is bucketed inside the group). The fault
    config is part of the key: guard/quorum are trace-time constants of
    the bucket program, and fault-free points must keep tracing the
    historical clean program (bitwise guarantee) rather than riding a
    faulted bucket with identity views."""
    T_, n = sc.D.shape
    return (sc.cfg.model, sc.cfg.eta, sc.cfg.tau,
            pl.bucket_rounds(T_, sc.cfg.tau, bucket),
            pl.bucket_size(n, bucket,
                           max_inflation=pl.BUCKET_MAX_INFLATION),
            sc.faults is not None,
            bool(sc.guard) if sc.faults is not None else False,
            float(sc.quorum) if sc.faults is not None else 0.0)


def _group_dims(prepared, tau: int, bucket: str) -> dict:
    """Padded bucket dims of one group (dense AND ragged stagings),
    computed from the prepared streams — the cost model's shape
    inputs."""
    processed_list = [p[1] for p in prepared]
    points = []
    for (st, processed, act_all, max_pts) in prepared:
        if isinstance(processed, pl.FlatStreams):
            T_, n = processed.T, processed.n
        else:
            T_, n = len(processed), len(processed[0])
        points.append((T_, n, int(max_pts)))
    cap = pl.BUCKET_MAX_INFLATION
    T_b = max(pl.bucket_rounds(T_, tau, bucket) for T_, _, _ in points)
    n_b = max(pl.bucket_size(n, bucket, max_inflation=cap)
              for _, n, _ in points)
    P_b = pl.bucket_size(max(P for _, _, P in points), bucket,
                         max_inflation=cap)
    rows = pl.ragged_rows(processed_list)
    R_b = pl.bucket_size(max(int(rows.max()) if rows.size else 1, 1),
                         bucket, max_inflation=cap)
    return {"points": points, "T_b": T_b, "n_b": n_b, "P_b": P_b,
            "R_b": R_b, "chunk": pl.RAGGED_CHUNK}


def _point_ident(sc: Scenario) -> tuple:
    """Prep-free identity of one point's compiled loop program: the
    config fields that determine its staged shapes (the stream seed
    fixes the Poisson sample counts, churn fixes the activity mask)."""
    cfg = sc.cfg
    return (cfg.T, cfg.n, cfg.seed, cfg.p_exit, cfg.p_entry)


def run_scenarios(scenarios: list[Scenario], scale: BenchScale, *,
                  train=True, engine="auto", iters=400, seed=0,
                  batch: bool | None = None, bucket: str = "pow2",
                  plans: list | None = None, mesh="auto",
                  staging: str | None = None) -> list[dict]:
    """Solve + evaluate + (optionally) train a whole sweep.

    Convex plans: one compiled program per (T, n) group. Training
    groups points into shape buckets (:func:`scenario_bucket_key`) and
    dispatches EACH bucket through the cost model
    (``core.costmodel``): predicted cost = padded work slots × per-slot
    cost + predicted compiles × measured compile cost, for the
    per-point loop, the dense-batched and the ragged-batched program
    (``run_network_aware_batched`` — vmapped scenario axis, whole-
    bucket eval drained by one stacked AsyncEvaluator dispatch).
    Single-point buckets short-circuit to the loop path. The decision
    is recorded in every row's ``"dispatch"`` field.

    ``engine="batched"`` (or ``batch=True``) forces every bucket onto
    the batched path; ``batch=False`` (or a per-point ``engine`` of
    "scan"/"sharded"/"legacy") keeps the original per-point dispatch
    loop — the oracle the batched path is equivalence-tested against.
    ``staging``: ``None`` defaults to cost-model choice under dispatch
    and to "dense" under a forced batched engine (preserving the
    historical bitwise contract); "auto" always lets the model pick
    dense vs ragged; "dense"/"ragged" pin the batched staging.
    ``plans`` short-circuits the solve (a bench that times both paths
    hands the same plans to each). ``mesh``: "auto" shards the batched
    path across all visible devices on multi-device hosts, ``None``
    forces single-device programs, an explicit mesh is used as-is
    (ragged staging requires a single-device program and is excluded
    from the choice when a mesh would be used).
    """
    import jax

    from repro.core import costmodel as cm
    from repro.core.engine import resolve_engine

    if plans is None:
        plans = solve_scenario_plans(scenarios, iters=iters, seed=seed)
    data = dataset(scale.n_train, scale.n_test)
    if batch is None:
        # explicit batch=False always wins (even with engine="batched",
        # which then runs per point through the S=1 bucket program)
        batch = engine in ("auto", "batched") and len(scenarios) > 1
    # cost-model dispatch only when nothing forces a path: the default
    # engine="auto" sweep; engine="batched" forces batched buckets
    force_batched = engine == "batched" or (batch and engine != "auto")
    hists: list = [None] * len(scenarios)
    engines: list = [("batched" if batch
                      else resolve_engine(engine or "auto"))] \
        * len(scenarios)
    dispatches: list = [None] * len(scenarios)
    # hierarchical points: the tier tree picks the compiled program, so
    # they train per point on the scan substrate and never join a
    # batched bucket
    hier_idx = {b for b, sc in enumerate(scenarios)
                if sc.hierarchy is not None}
    if train and hier_idx:
        for b in sorted(hier_idx):
            sc = scenarios[b]
            hists[b] = F.run_network_aware(
                sc.cfg, data, sc.traces, sc.adj, plans[b],
                streams=sc.streams, activity=sc.activity,
                schedule=sc.schedule, engine="scan", faults=sc.faults,
                guard=sc.guard, quorum=sc.quorum,
                hierarchy=sc.hierarchy)
            engines[b] = "hierarchical"
    if train and batch:
        cm.install_listener()
        allow_ragged = mesh is None or (mesh == "auto"
                                        and jax.device_count() == 1)
        groups: dict[tuple, list[int]] = {}
        for b, sc in enumerate(scenarios):
            if b in hier_idx:
                continue
            groups.setdefault(scenario_bucket_key(sc, bucket=bucket),
                              []).append(b)
        for gkey, idxs in groups.items():
            fault_list = [scenarios[b].faults for b in idxs]
            any_faults = any(f is not None for f in fault_list)
            # the sweep's own prep: each point's host data plane is a
            # ``prep`` span of _prepare_streams
            prepared = []
            for b in idxs:
                sc = scenarios[b]
                prepared.append(F._prepare_streams(
                    sc.cfg, data, plans[b], sc.streams, sc.activity,
                    sc.schedule, sc.faults))
            tau = scenarios[idxs[0]].cfg.tau
            dims = _group_dims(prepared, tau, bucket)
            dims["idents"] = [_point_ident(scenarios[b]) for b in idxs]
            # test-eval work is path-independent: Σ windows × n_test
            dims["eval_slots"] = sum(T_ // tau for T_, _, _
                                     in dims["points"]) * scale.n_test
            pin = staging
            if pin is None:
                # forced batched keeps the historical dense staging
                # (its bitwise contract); dispatch mode lets the model
                # choose
                pin = "dense" if force_batched else "auto"
            if pin == "auto" and not allow_ragged:
                pin = "dense"
            decision = cm.MODEL.choose(
                key=gkey, force_path="batched" if force_batched
                else None, staging=None if pin == "auto" else pin,
                **dims)
            t0 = time.perf_counter()
            compiles0 = cm.MODEL.compile_events
            if decision.path == "batched":
                outs = F.run_network_aware_batched(
                    [scenarios[b].cfg for b in idxs], data,
                    [plans[b] for b in idxs],
                    streams=[scenarios[b].streams for b in idxs],
                    activities=[scenarios[b].activity for b in idxs],
                    schedules=[scenarios[b].schedule for b in idxs],
                    mesh=mesh, bucket=bucket, staging=decision.staging,
                    prepared=prepared,
                    faults=fault_list if any_faults else None,
                    # the bucket key groups by (guard, quorum), so the
                    # group's config is any member's config
                    guard=scenarios[idxs[0]].guard,
                    quorum=scenarios[idxs[0]].quorum)
                for b, hist in zip(idxs, outs):
                    hists[b] = hist
                    engines[b] = "batched"
            else:
                loop_engine = resolve_engine("auto")
                for i, b in enumerate(idxs):
                    sc = scenarios[b]
                    hists[b] = F.run_network_aware(
                        sc.cfg, data, sc.traces, sc.adj, plans[b],
                        streams=sc.streams, activity=sc.activity,
                        schedule=sc.schedule, engine=loop_engine,
                        mesh=None if mesh == "auto" else mesh,
                        faults=sc.faults, guard=sc.guard,
                        quorum=sc.quorum, prepared=prepared[i])
                    engines[b] = loop_engine
            ran = ("loop" if decision.path == "loop"
                   else f"batched-{decision.staging}")
            cm.MODEL.observe_run(
                decision.path, decision.staging,
                decision.slots.get(ran, 0), time.perf_counter() - t0,
                cm.MODEL.compile_events - compiles0,
                n_points=len(idxs), eval_slots=dims["eval_slots"])
            cm.MODEL.record(decision, key=gkey, **dims)
            for b in idxs:
                dispatches[b] = decision.as_row()
    elif train:
        point_mesh = None if mesh == "auto" else mesh
        for b, (sc, plan) in enumerate(zip(scenarios, plans)):
            if b in hier_idx:
                continue
            if engines[b] == "batched":
                # per point through the bucket program, one scenario
                # at its exact shape
                hists[b] = F.run_network_aware_batched(
                    [sc.cfg], data, [plan], streams=[sc.streams],
                    activities=[sc.activity], schedules=[sc.schedule],
                    mesh=point_mesh, bucket="exact",
                    faults=None if sc.faults is None else [sc.faults],
                    guard=sc.guard, quorum=sc.quorum)[0]
                continue
            hists[b] = F.run_network_aware(sc.cfg, data, sc.traces,
                                           sc.adj, plan,
                                           streams=sc.streams,
                                           activity=sc.activity,
                                           schedule=sc.schedule,
                                           engine=engines[b],
                                           mesh=point_mesh,
                                           faults=sc.faults,
                                           guard=sc.guard,
                                           quorum=sc.quorum)
        # a forced loop sweep compiles its per-point programs: tell
        # the cost model, so later dispatched sweeps price the loop
        # path as warm
        for b, sc in enumerate(scenarios):
            if b in hier_idx:
                continue
            cm.MODEL.mark_loop_seen(
                scenario_bucket_key(sc, bucket=bucket),
                [_point_ident(sc)])
    rows = []
    for b, (sc, plan, hist) in enumerate(zip(scenarios, plans, hists)):
        cost = mv.plan_cost(plan, sc.traces, sc.D,
                            error_model=sc.error_model, gamma=sc.gamma)
        out = {**sc.key, "setting": sc.setting, "cost": cost,
               "engine": engines[b]}
        if dispatches[b] is not None:
            out["dispatch"] = dispatches[b]
        if hist is not None:
            out.update(acc=hist["test_acc"][-1],
                       acc_curve=hist["test_acc"],
                       sim_before=hist["sim_before"],
                       sim_after=hist["sim_after"],
                       avg_active=float(np.mean([a.sum()
                                                 for a in hist["active"]])))
            if sc.faults is not None:
                out["fault_summary"] = sc.faults.summary()
                out["quorum_skips"] = int(sum(
                    not ok for ok in hist.get("agg_quorum_ok", [])))
        rows.append(out)
    return rows


def fog_experiment(*, scale: BenchScale, n=10, model="mlp", iid=True,
                   costs="testbed", topology="full", rho=1.0,
                   setting="B", error_model="discard", medium="wifi",
                   p_exit=0.0, p_entry=0.0, f_err=0.7, seed=0,
                   train=True) -> dict:
    """One full experiment; returns accuracy + cost decomposition."""
    rng = np.random.default_rng(seed)
    data = dataset(scale.n_train, scale.n_test)
    cfg = F.FedConfig(n=n, T=scale.T, tau=scale.tau, eta=scale.eta,
                      model=model, iid=iid, seed=seed,
                      p_exit=p_exit, p_entry=p_entry)
    if costs == "testbed":
        traces = testbed_like_costs(n, scale.T, rng, f_err=f_err,
                                    medium=medium)
    else:
        traces = synthetic_costs(n, scale.T, rng, f_err=f_err)
    adj = make_topology(topology, n, rng, rho=rho,
                        costs=traces.c_node.mean(0))
    streams = pl.poisson_streams(n, scale.T, data[1], iid=iid, rng=rng)
    D = pl.counts(streams)
    if setting in ("D", "E"):
        traces = with_capacity(traces, float(D.mean()))
    plan = make_plan(setting, traces, adj, D, error_model=error_model)
    cost = mv.plan_cost(plan, traces, D, error_model=error_model)
    out = {"setting": setting, "cost": cost, "n": n, "rho": rho,
           "tau": scale.tau, "topology": topology, "iid": iid}
    if train:
        activity = (F.churn_activity(cfg, rng)
                    if (p_exit or p_entry) else None)
        hist = F.run_network_aware(cfg, data, traces, adj, plan,
                                   streams=streams, activity=activity)
        out.update(acc=hist["test_acc"][-1],
                   acc_curve=hist["test_acc"],
                   sim_before=hist["sim_before"],
                   sim_after=hist["sim_after"],
                   avg_active=float(np.mean([a.sum()
                                             for a in hist["active"]])))
    return out
