"""Network-aware federated learning (paper §III-B + §V).

Paper-faithful scale: every fog device i holds its own parameters w_i(t),
realized as a stacked pytree with a leading device axis and a vmapped
local SGD step (eq. 3). Aggregation (eq. 4) is the H_i-weighted average
over contributing devices every τ rounds, followed by synchronization.
Data offloading/discarding is applied to the physical sample streams by
``data/pipeline.apply_movement`` before training.

The training loop itself lives in :mod:`repro.core.engine`:
``run_network_aware`` is a thin wrapper that prepares the sample streams
on the host and dispatches to one of three engines: the scan-compiled
engine (``engine="scan"``, the default; ``hierarchy=`` selects its
tiered program), the device-sharded engine (``engine="sharded"`` —
shard_map over a "data" mesh, psum aggregation, eval streamed off the
hot path) or the legacy per-round loop (``engine="legacy"``, kept as
oracle/baseline). ``run_network_aware_batched`` trains a sweep bucket
in one program.

Baselines: ``centralized`` (all data at one node) and ``federated``
(no movement, G_i = D_i) — both used by the Table II/III benchmarks.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as eng
from repro.core import monitoring
from repro.core import movement as mv
from repro.core import sanitize as sz
from repro.core.costs import CostTraces
from repro.core.engine import (_stack, _sync, aggregate,  # noqa: F401
                               make_device_step, make_model)
from repro.core.schedule import NetworkSchedule
from repro.core.topology import churn_schedule
from repro.data import pipeline as pl
from repro.models import mnist as mm


@dataclasses.dataclass
class FedConfig:
    n: int = 10
    T: int = 100
    tau: int = 10
    eta: float = 0.01
    model: str = "cnn"
    iid: bool = True
    seed: int = 0
    max_points: int = 0          # pad size; 0 -> auto from streams
    p_exit: float = 0.0
    p_entry: float = 0.0
    eval_every: int = 10


def run_network_aware(cfg: FedConfig, data, traces: CostTraces,
                      adj: np.ndarray | None, plan: mv.MovementPlan,
                      streams: pl.FogStreams | None = None,
                      activity: np.ndarray | None = None,
                      engine: str = "scan", mesh=None,
                      schedule: NetworkSchedule | None = None,
                      faults=None, guard: bool = True,
                      quorum: float = 0.0,
                      checkpoint_path: str | None = None,
                      checkpoint_every: int = 1,
                      resume: str | None = None,
                      stop_after: int | None = None,
                      prepared: tuple | None = None,
                      sanitize=False, hierarchy=None) -> dict:
    """Train with a given movement plan. Returns history dict.

    ``adj`` is accepted for signature symmetry with the planning layer
    (the plan was solved against it) but training itself never reads
    it — pass ``None`` rather than materializing a dense matrix.

    ``sanitize`` — ``True`` or a :class:`repro.core.sanitize.
    SanitizeConfig`: runs the engine under jax's runtime checkers
    (``debug_nans``, optional tracer-leak checking, a transfer guard
    around compiled-program dispatch, and a warm-recompile watchdog
    when ``expect_warm`` is set). Small-n smoke harness — the debug
    flags change jit cache keys and disable some optimizations, so
    don't benchmark under it.

    ``prepared`` — optional precomputed ``_prepare_streams`` result
    (streams, processed, act_all, max_pts) for THIS scenario: skips
    the host data-plane prep, so a sweep driver that already staged
    the point (e.g. to price it for dispatch) doesn't pay it twice.

    ``schedule`` — optional :class:`NetworkSchedule`: the per-round
    active mask every engine stages (and the churn masking inside the
    scan bodies) derives from ``schedule.activity()`` — one source of
    truth shared with the movement plane that planned against the same
    schedule. A constant schedule reproduces the static path bitwise.
    ``activity`` (T, n) bool — explicit churn trace (§V-E); overrides
    the schedule's mask when both are given (legacy path); inactive
    devices collect nothing, don't train, and miss aggregations.
    ``engine`` — "scan" (one compiled lax.scan over all rounds),
    "sharded" (the scan partitioned across a "data" device mesh via
    shard_map, aggregation as a cross-shard psum, eval streamed off the
    hot path — see ``core.engine.run_rounds_sharded``), "legacy" (the
    original per-round loop, kept as the numerical oracle), or "auto"
    (sharded on multi-device hosts, scan otherwise).
    ``mesh`` — optional 1-D "data" mesh for the sharded engine
    (default: ``launch.mesh.make_data_mesh()`` over all visible
    devices; n is padded to a mesh multiple with phantom inactive
    devices).

    The scan engine pins ``x_tr``/``x_te``/``y_te`` device-resident
    across calls (keyed by identity + a sampled checksum): treat the
    arrays in ``data`` as immutable between calls — a sparse in-place
    edit that slips past the checksum would train on stale pixels.

    ``faults`` — optional :class:`repro.core.faults.FaultSchedule`
    (unannounced failures): crash outages stop data collection and
    training like unplanned churn, and straggled/dropped/corrupted
    uploads are injected inside the engine's aggregation, guarded by
    ``guard`` (finite-masking + survivor renormalization) and gated by
    ``quorum`` (windows whose surviving-upload fraction falls below it
    carry the previous global forward). The returned history gains
    ``fault_summary``/``agg_survivors``/``agg_quorum_ok``.

    ``checkpoint_path``/``checkpoint_every``/``resume``/``stop_after``
    — window-boundary checkpointing of the flat scan engine (see
    ``core.engine.run_rounds_scan``); other engines and tier trees
    reject them.

    ``hierarchy`` — optional :class:`repro.core.hierarchy.TierTree`:
    aggregation composes up the tier tree in the scan engine's tiered
    program (``core.engine.run_rounds_scan(tree=)``), with the tree's
    device count required to equal ``cfg.n`` and its first tier period
    ``cfg.tau``. Only ``engine`` values "scan"/"auto" compose with it;
    an L=1 tree reproduces the flat scan bitwise.
    """
    x_tr, y_tr, x_te, y_te = data
    if prepared is not None:
        streams, processed, act_all, max_pts = prepared
    else:
        streams, processed, act_all, max_pts = _prepare_streams(
            cfg, data, plan, streams, activity, schedule, faults)

    extra = {}
    if hierarchy is not None:
        if engine not in ("auto", "scan"):
            raise ValueError("hierarchy= runs on the scan engine; "
                             f"got engine={engine!r}")
        engine = "scan"
        extra["hierarchy"] = {"levels": hierarchy.levels,
                              "group_counts": list(hierarchy.group_counts),
                              "taus": list(hierarchy.taus)}
    else:
        engine = eng.resolve_engine(engine)
    if isinstance(streams, pl.FlatStreams) and engine != "scan":
        raise ValueError("FlatStreams sparse staging is a scan-engine "
                         f"feature; got engine={engine!r}")
    fault_kw = {}
    if faults is not None:
        fault_kw = dict(faults=faults, guard=guard, quorum=quorum)
        extra["fault_summary"] = faults.summary()
    ckpt_kw = {}
    if (checkpoint_path is not None or resume is not None
            or stop_after is not None):
        if engine != "scan":
            raise ValueError(
                "checkpoint/resume is a scan-engine feature; got "
                f"engine={engine!r}")
        ckpt_kw = dict(checkpoint_path=checkpoint_path,
                       checkpoint_every=checkpoint_every,
                       resume=resume, stop_after=stop_after)
    runners = {"scan": functools.partial(eng.run_rounds_scan,
                                         tree=hierarchy),
               "sharded": functools.partial(eng.run_rounds_sharded,
                                            mesh=mesh),
               "legacy": eng.run_rounds_legacy}
    if engine not in runners:
        raise ValueError(f"unknown engine {engine!r}; "
                         f"expected one of {sorted(runners)} or 'auto'")
    runner = runners[engine]
    with monitoring.span("train"):
        w_global, apply_fn = make_model(cfg.model,
                                        jax.random.PRNGKey(cfg.seed))
        hist = _history_base(cfg, y_tr, streams, processed, act_all)
        hist.update(extra)
        with sz.sanitized(sanitize):
            hist.update(runner(apply_fn, w_global, x_tr, y_tr, x_te, y_te,
                               processed, act_all, cfg.tau, cfg.eta,
                               max_pts, **fault_kw, **ckpt_kw))
    return hist


def _prepare_streams(cfg: FedConfig, data, plan, streams, activity,
                     schedule, faults=None):
    """Host-side data-plane prep shared by the single and batched run
    paths: default streams, schedule→activity, fault-outage masking,
    inactive-collection zeroing, movement routing, pad sizing.

    ``streams`` may be a :class:`repro.data.pipeline.FlatStreams` — the
    sparse staging path: activity masking, bang-bang movement routing
    and round staging all run as vectorized array ops over the flat
    sample table (O(samples)), so nothing O(n²) — and no (n, n) array
    at all — is built on the way into the compiled engine."""
    with monitoring.span("prep"):
        _, y_tr, _, _ = data
        rng = np.random.default_rng(cfg.seed)
        if streams is None:
            streams = pl.poisson_streams(cfg.n, cfg.T, y_tr, iid=cfg.iid,
                                         rng=rng)
        if schedule is not None:
            if (schedule.T, schedule.n) != (cfg.T, cfg.n):
                raise ValueError(
                    f"schedule is (T={schedule.T}, n={schedule.n}) but the "
                    f"run is (T={cfg.T}, n={cfg.n})")
            if activity is None:
                activity = schedule.activity()
        if faults is not None and faults.has_crashes:
            # a crashed device stops collecting/training like a churned one
            # — except nobody announced it (no replanning saw it coming)
            if (faults.T, faults.n) != (cfg.T, cfg.n):
                raise ValueError(
                    f"fault schedule is (T={faults.T}, n={faults.n}) but "
                    f"the run is (T={cfg.T}, n={cfg.n})")
            base = (np.asarray(activity, bool) if activity is not None
                    else np.ones((cfg.T, cfg.n), bool))
            activity = base & faults.activity_mask()
        if isinstance(streams, pl.FlatStreams):
            if activity is not None:
                act = np.asarray(activity, bool)
                keep = act[streams.t, streams.dev]
                streams = pl.FlatStreams(t=streams.t[keep],
                                         dev=streams.dev[keep],
                                         idx=streams.idx[keep],
                                         n=streams.n, T=streams.T)
            processed = pl.apply_movement_flat(streams, plan, rng)
        else:
            if activity is not None:
                # inactive devices collect nothing (no-op for all-active
                # masks, e.g. a constant schedule)
                for t, i in zip(*np.nonzero(~np.asarray(activity, bool))):
                    streams.collected[t][i] = np.empty(0, np.int64)
            processed = pl.apply_movement(streams, plan, rng)
        max_pts = pl.pad_size(processed, cfg.max_points)
        act_all = (np.asarray(activity, bool) if activity is not None
                   else np.ones((cfg.T, cfg.n), bool))
        return streams, processed, act_all, max_pts


def _history_base(cfg: FedConfig, y_tr, streams, processed,
                  act_all) -> dict:
    """History skeleton: rounds, Fig. 4b label-similarity diagnostics,
    activity masks and processed counts (the engine fills the rest).

    On the flat-stream path the O(n²) pairwise label-similarity
    diagnostics are skipped (``None``) — they are a small-n figure, and
    computing them at fog scale would defeat the sparse staging."""
    with monitoring.span("train.history"):
        hist = {"round": list(range(cfg.T)), "sim_before": None,
                "sim_after": None}
        hist["active"] = [act_all[t].copy() for t in range(cfg.T)]
        if isinstance(processed, pl.FlatStreams):
            cnt = np.bincount(processed.cell_key(),
                              minlength=cfg.T * cfg.n).reshape(cfg.T, cfg.n)
            hist["processed_counts"] = [row for row in cnt]
            return hist
        col_labels = [np.concatenate([y_tr[ix] for row in streams.collected
                                      for ix in [row[i]]]
                                     or [np.empty(0, int)])
                      for i in range(cfg.n)]
        proc_labels = [np.concatenate([y_tr[processed[t][i]]
                                       for t in range(cfg.T)]
                                      or [np.empty(0, int)])
                       for i in range(cfg.n)]
        hist["sim_before"] = pl.label_similarity(col_labels)
        hist["sim_after"] = pl.label_similarity(proc_labels)
        hist["processed_counts"] = [[len(ix) for ix in processed[t]]
                                    for t in range(cfg.T)]
        return hist


def run_network_aware_batched(cfgs: list[FedConfig], data,
                              plans: list[mv.MovementPlan], *,
                              streams: list | None = None,
                              activities: list | None = None,
                              schedules: list | None = None,
                              mesh="auto", bucket: str = "pow2",
                              staging: str = "dense",
                              prepared: list | None = None,
                              faults: list | None = None,
                              guard: bool = True,
                              quorum: float = 0.0) -> list[dict]:
    """Train a whole bucket of sweep points in ONE compiled program.

    The batched counterpart of looping ``run_network_aware`` over a
    sweep: per-scenario host prep (streams, schedule masking, movement
    routing — identical code path, so the staged streams are
    bitwise-identical to the loop) feeds
    ``core.engine.run_rounds_batched``, which pads every point up to
    the shared shape bucket and vmaps the scenario axis over one window
    scan (sharded across the "data" mesh on multi-device hosts). All
    scenarios must share the dataset, model, η and τ — group a
    heterogeneous sweep into buckets first
    (``benchmarks.fog.scenario_bucket_key``).

    ``mesh="auto"`` shards the fog-device axis across all visible
    devices on multi-device hosts; ``mesh=None`` forces the
    single-device program; an explicit mesh is used as-is.

    ``staging`` — "dense" pads every point to the bucket's (n_b, P_b)
    slab; "ragged" stages chunk-row tables so compiled work tracks the
    actual sample total (single-program only — the cost-model dispatch
    in ``benchmarks.fog.run_scenarios`` picks between them per bucket).

    ``prepared`` — optional pre-computed ``_prepare_streams`` results
    (one ``(streams, processed, act_all, max_pts)`` tuple per
    scenario): the cost-model dispatch runs the host prep once to price
    the bucket and hands it down here, so dispatching never pays prep
    twice.

    Returns one history dict per scenario, same contract as
    ``run_network_aware``.
    """
    S = len(cfgs)
    if not (S == len(plans)
            and all(lst is None or len(lst) == S
                    for lst in (streams, activities, schedules,
                                faults))):
        raise ValueError("cfgs/plans/streams/activities/schedules/"
                         "faults must have one entry per scenario")
    head = (cfgs[0].model, cfgs[0].eta, cfgs[0].tau)
    for cfg in cfgs[1:]:
        if (cfg.model, cfg.eta, cfg.tau) != head:
            raise ValueError(
                "a batched bucket must share (model, eta, tau); got "
                f"{(cfg.model, cfg.eta, cfg.tau)} vs {head}")

    x_tr, y_tr, x_te, y_te = data
    pl.reset_padding_warnings()          # inflation warnings: once/sweep
    processed_list, act_list, max_list, hists = [], [], [], []
    for b, cfg in enumerate(cfgs):
        f = faults[b] if faults is not None else None
        if prepared is not None:
            st, processed, act_all, max_pts = prepared[b]
        else:
            st, processed, act_all, max_pts = _prepare_streams(
                cfg, data, plans[b],
                streams[b] if streams is not None else None,
                activities[b] if activities is not None else None,
                schedules[b] if schedules is not None else None, f)
        processed_list.append(processed)
        act_list.append(act_all)
        max_list.append(max_pts)
        h = _history_base(cfg, y_tr, st, processed, act_all)
        if f is not None:
            h["fault_summary"] = f.summary()
        hists.append(h)

    models = [make_model(cfg.model, jax.random.PRNGKey(cfg.seed))
              for cfg in cfgs]
    params_list = [params for params, _ in models]
    apply_fn = models[0][1]
    outs = eng.run_rounds_batched(
        apply_fn, params_list, x_tr, y_tr, x_te, y_te, processed_list,
        act_list, cfgs[0].tau, cfgs[0].eta, max_list, bucket=bucket,
        mesh=mesh, staging=staging, faults=faults, guard=guard,
        quorum=quorum)
    for hist, out in zip(hists, outs):
        hist.update(out)
    return hists


def run_centralized(cfg: FedConfig, data, steps: int | None = None,
                    batch: int = 600) -> dict:
    """All data processed at one node (Table II 'Centralized')."""
    x_tr, y_tr, x_te, y_te = data
    key = jax.random.PRNGKey(cfg.seed)
    params, apply_fn = make_model(cfg.model, key)
    steps = steps or cfg.T

    @jax.jit
    def st(p, x, y):
        def lf(q):
            return mm.ce_loss(apply_fn(q, x), y)

        loss, g = jax.value_and_grad(lf)(p)
        return jax.tree_util.tree_map(lambda a, b: a - cfg.eta * b, p, g), loss

    rng = np.random.default_rng(cfg.seed)
    losses = []
    for _ in range(steps):
        idx = rng.choice(len(x_tr), batch, replace=False)
        params, loss = st(params, jnp.asarray(x_tr[idx]),
                          jnp.asarray(y_tr[idx]))
        losses.append(float(loss))
    logits = apply_fn(params, jnp.asarray(x_te))
    return {"test_acc": float(mm.accuracy(logits, jnp.asarray(y_te))),
            "test_loss": float(mm.ce_loss(logits, jnp.asarray(y_te))),
            "train_loss": losses}


def run_federated(cfg: FedConfig, data, **kw) -> dict:
    """No-movement baseline: G_i(t) = D_i(t)."""
    plan = mv.no_movement_plan(cfg.T, cfg.n)
    traces = kw.pop("traces", None)
    # no-movement training never reads the adjacency: don't default to
    # a dense (n, n) ones matrix (10 GB at n=10⁵) nobody looks at
    adj = kw.pop("adj", None)
    if traces is None:
        from repro.core.costs import synthetic_costs
        traces = synthetic_costs(cfg.n, cfg.T, np.random.default_rng(cfg.seed))
    return run_network_aware(cfg, data, traces, adj, plan, **kw)


def churn_activity(cfg: FedConfig, rng: np.random.Generator) -> np.ndarray:
    """Legacy (T, n) churn trace — now just the active mask of the
    ChurnProcess-produced :class:`NetworkSchedule` (identical rng
    stepping), so the engine masking and the movement plane share one
    producer."""
    # foglint: disable=dense-materialization -- legacy compat shim: churn_schedule takes a dense base adjacency by contract and every caller is small-n
    sched = churn_schedule(np.ones((cfg.n, cfg.n), bool), cfg.T,
                           cfg.p_exit, cfg.p_entry, rng, tau=cfg.tau)
    return sched.activity()
