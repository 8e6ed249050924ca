"""Training launcher.

Two modes:

* ``fog`` — the paper's experiment: network-aware federated learning of
  an image classifier over n fog devices (vmapped device axis), with the
  data-movement optimizer in the loop.

      python -m repro.launch.train --mode fog --model cnn --n 10 --T 100 \
          --tau 10 --topology full --setting B --costs testbed

* ``lm``  — production-scale integration: train a (reduced) assigned
  architecture on synthetic tokens with the network-aware data pipeline:
  per-shard heterogeneous costs -> movement plan -> route/weights inputs
  -> H_i-weighted loss. Run under however many host devices exist
  (XLA_FLAGS=--xla_force_host_platform_device_count=8 for a multi-shard
  CPU demo).

      python -m repro.launch.train --mode lm --arch qwen3-14b --smoke \
          --steps 40 --batch 8 --seq 128 --data-shards 4
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import InputShape
from repro.configs.registry import get_config
from repro.core import estimator as est
from repro.core import faults as fl
from repro.core import federated as F
from repro.core import movement as mv
from repro.core.costs import synthetic_costs, testbed_like_costs, with_capacity
from repro.core.topology import make_schedule, make_topology
from repro.data import pipeline as pl
from repro.data.synthetic import make_image_dataset, make_token_dataset
from repro.launch import steps as St
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.models.module import init_params
from repro.optim import optimizers as opt_lib


def solve_setting(setting: str, traces, adj, D, error_model="discard"):
    """Paper Table III settings:
    A no movement; B perfect info; C imperfect info;
    D perfect + capacity; E imperfect + capacity."""
    T_, n = D.shape
    if setting == "A":
        return mv.no_movement_plan(T_, n)
    if setting in ("D", "E"):
        traces = with_capacity(traces, float(D.mean()))
    tr = traces
    if setting in ("C", "E"):
        tr = est.estimate_traces(traces)
    if error_model == "discard":
        plan = mv.greedy_linear(tr, adj)
    else:
        plan = mv.solve_convex(tr, adj, est.estimate_counts(D)
                               if setting in ("C", "E") else D,
                               error_model=error_model)
    if setting in ("D", "E"):
        plan = mv.repair_capacities(plan, traces, adj, D)
    return plan


def run_fog(args) -> dict:
    rng = np.random.default_rng(args.seed)
    data = make_image_dataset(n_train=args.n_train, n_test=args.n_test,
                              seed=args.seed)
    sched_kind = args.schedule
    p_exit, p_entry = args.p_exit, args.p_entry
    if args.churn:                       # shorthand for a symmetric churn
        sched_kind = "churn"
        p_exit = p_exit or args.churn
        p_entry = p_entry or args.churn
    if sched_kind == "static" and (p_exit or p_entry):
        sched_kind = "churn"             # legacy --p-exit/--p-entry path
    if sched_kind == "flap" and (p_exit or p_entry):
        raise SystemExit("--schedule flap does not model node churn; "
                         "drop --p-exit/--p-entry/--churn or use "
                         "--schedule churn")
    cfg = F.FedConfig(n=args.n, T=args.T, tau=args.tau, eta=args.eta,
                      model=args.model, iid=not args.non_iid, seed=args.seed,
                      p_exit=p_exit, p_entry=p_entry)
    mk = testbed_like_costs if args.costs == "testbed" else synthetic_costs
    traces = mk(cfg.n, cfg.T, rng, f_err=args.f_err)
    adj = make_topology(args.topology, cfg.n, rng,
                        rho=args.rho, costs=traces.c_node.mean(0))
    streams = pl.poisson_streams(cfg.n, cfg.T, data[1], iid=cfg.iid, rng=rng)
    D = pl.counts(streams)
    schedule = make_schedule(sched_kind, adj, cfg.T, rng,
                             p_exit=p_exit, p_entry=p_entry,
                             p_flap=args.p_flap, p_recover=args.p_recover,
                             tau=cfg.tau)
    dynamic = schedule.static_adj is None
    # what the planner sees (--replan): the true schedule ("oracle",
    # replan-on-event), the schedule predicted from the observed
    # history ("predict", setting-C imperfect information applied to
    # the network itself), or the static base graph ("once" /
    # --plan-once). Execution and costing always run on the TRUE
    # schedule: predictive and plan-once plans are realized against it
    # — data over dead links or toward churned-out receivers is lost
    if args.plan_once and args.replan not in ("oracle", "once"):
        raise SystemExit(f"--plan-once conflicts with --replan "
                         f"{args.replan}; drop one of the two")
    replan = "once" if args.plan_once else args.replan
    if not dynamic:
        replan = "oracle"                # static network: modes coincide
    plan_network = (schedule if replan == "oracle" else
                    est.predict_schedule(schedule)
                    if replan == "predict" else adj)
    plan = solve_setting(args.setting, traces, plan_network, D,
                         error_model=args.error_model)
    # unannounced faults: never visible to the planner — crash outages
    # only change the EXECUTED network (realization + engine masking),
    # upload faults only the engine's guarded aggregation. A separate
    # rng stream (seed + 7919) keeps streams/costs/topology bitwise
    # identical to the fault-free run
    faults = fl.make_faults(args.faults, cfg.T, cfg.n, cfg.tau,
                            rate=args.fault_rate, seed=args.seed + 7919,
                            corrupt=args.corrupt_mode)
    if faults is not None and faults.has_crashes:
        plan = mv.realize_plan(plan, faults.compose(
            schedule if dynamic else None, adj=adj))
    elif dynamic:
        plan = mv.realize_plan(plan, schedule)   # no-op for oracle greedy
    from repro.core.engine import resolve_engine

    engine = resolve_engine(args.engine)
    if (args.checkpoint or args.resume) and args.engine == "auto":
        engine = "scan"                  # checkpointing is scan-only
    hierarchy = None
    if args.tiers:
        from repro.core import hierarchy as hr

        hierarchy = hr.TierTree.from_spec(args.tiers, cfg.n)
        if hierarchy.taus[0] != cfg.tau:
            raise SystemExit(f"--tiers first period "
                             f"{hierarchy.taus[0]} must equal --tau "
                             f"{cfg.tau}")
        if args.engine == "auto":
            engine = "scan"              # the tree picks the program
    run_kw = dict(streams=streams, schedule=schedule, engine=engine,
                  faults=faults, guard=not args.unguarded,
                  quorum=args.quorum, checkpoint_path=args.checkpoint,
                  resume=args.resume, hierarchy=hierarchy)
    sanitize_report = None
    if args.sanitize:
        from repro.core import sanitize as sz

        # runtime-sanitized smoke: a cold pass under the sanitizer
        # (the debug flags are part of jit's cache key, so this pass
        # compiles the programs the warm pass will reuse), then a warm
        # re-run that raises RecompileError if anything compiles —
        # plus transfer_guard("disallow") around the staged hot loop
        # and debug_nans on both passes
        F.run_network_aware(cfg, data, traces, adj, plan,
                            sanitize=True, **run_kw)
        warm = sz.SanitizeConfig(expect_warm=True)
        hist = F.run_network_aware(cfg, data, traces, adj, plan,
                                   sanitize=warm, **run_kw)
        sanitize_report = {
            "transfer_guard": True, "debug_nans": True,
            "warm_compiles": int(getattr(warm, "last_compiles", 0))}
    else:
        hist = F.run_network_aware(cfg, data, traces, adj, plan,
                                   **run_kw)
    cost = mv.plan_cost(plan, traces, D, error_model=args.error_model)
    out = {"mode": "fog", "setting": args.setting, "engine": engine,
           "schedule": sched_kind, "replan": replan,
           "n_events": len(schedule.events_in(0, cfg.T)),
           "final_acc": hist["test_acc"][-1] if hist["test_acc"] else None,
           "acc_curve": hist["test_acc"], "cost": cost,
           "sim_before": hist["sim_before"], "sim_after": hist["sim_after"]}
    if hierarchy is not None:
        out["engine"] = "hierarchical"
        out["hierarchy"] = hist["hierarchy"]
    if faults is not None:
        out["fault_summary"] = hist["fault_summary"]
        out["quorum_skips"] = int(sum(
            not ok for ok in hist.get("agg_quorum_ok", [])))
    if sanitize_report is not None:
        out["sanitize"] = sanitize_report
    print(json.dumps(out, default=float, indent=2))
    return out


def lm_movement_inputs(n_shards: int, batch: int, T_rounds: int,
                       rng: np.random.Generator, het: float = 0.5):
    """Movement plan across data shards -> per-round (route, weights).

    Shards have heterogeneous per-point costs (straggler factors); links
    are the ICI (cheap, uniform). The Thm-3 greedy decides which shards'
    samples move; route permutes the global batch accordingly and weights
    zero out discarded samples.
    """
    from repro.core.costs import ici_costs
    speed = 1.0 + het * rng.standard_normal(n_shards).clip(-0.9, 4.0)
    traces = ici_costs(n_shards, T_rounds, bytes_per_point=4 * 2048,
                       flops_per_point=5e9, speed_factors=speed.clip(0.2),
                       f_err=1e9)  # critical task: never discard
    # scale c_node to comparable magnitude as c_link for interesting plans
    traces.c_node[:] *= 1e6
    traces.c_link[:] *= 1e6
    adj = make_topology("full", n_shards, rng)
    plan = mv.greedy_linear(traces, adj)
    per_shard = batch // n_shards
    routes, weights = [], []
    for t in range(T_rounds):
        dest = np.repeat(np.arange(n_shards), per_shard)
        for i in range(n_shards):
            # foglint: disable=dense-materialization -- LM-demo sharding: n here is the shard count (≤ 8), not the fog-device axis
            j = int(np.argmax(plan.s[t, i]))
            if j != i:  # shard i's samples processed by shard j
                dest[i * per_shard:(i + 1) * per_shard] = j
        order = np.argsort(dest, kind="stable")
        routes.append(order.astype(np.int32))
        w = np.ones(batch, np.float32)
        for i in range(n_shards):
            w[i * per_shard:(i + 1) * per_shard] = 1.0 - plan.r[t, i]
        weights.append(w[order])
    return plan, traces, routes, weights


def run_lm(args) -> dict:
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.layers:
        cfg = cfg.with_overrides(num_layers=args.layers)
    n_dev = jax.device_count()
    shards = min(args.data_shards, n_dev)
    rng = np.random.default_rng(args.seed)
    toks = make_token_dataset(args.steps * args.batch * (args.seq + 1) + 1,
                              cfg.vocab_size, seed=args.seed)

    params = init_params(T.specs(cfg), jax.random.PRNGKey(args.seed),
                         jnp.float32)
    opt = opt_lib.get_optimizer(args.optimizer, args.lr)
    opt_state = opt.init(params)
    plan, traces, routes, weights = lm_movement_inputs(
        shards, args.batch, args.steps, rng)

    def batch_at(it):
        off = it * args.batch * (args.seq + 1)
        chunk = toks[off: off + args.batch * (args.seq + 1)]
        chunk = chunk.reshape(args.batch, args.seq + 1)
        batch = {"tokens": jnp.asarray(chunk[:, :-1]),
                 "labels": jnp.asarray(chunk[:, 1:]),
                 "weights": jnp.asarray(weights[it]),
                 "route": jnp.asarray(routes[it])}
        if cfg.family == "encdec":
            batch["frames"] = jnp.zeros(
                (args.batch, cfg.encoder_seq, cfg.d_model), jnp.float32)
        if cfg.vision_patches:
            batch["patch_embeds"] = jnp.zeros(
                (args.batch, cfg.vision_patches, cfg.d_model), jnp.float32)
        return batch

    losses = []
    t0 = time.time()
    if args.lm_tau > 1:
        # FedAvg with tau local steps per round (paper eq. 3-4 at
        # production scale; shard_map over the data axis)
        from repro.distributed.fedavg import make_fedavg_round

        mesh = jax.make_mesh((shards,), ("data",))
        rnd = make_fedavg_round(cfg, opt, args.lm_tau, mesh)
        n_rounds = args.steps // args.lm_tau
        for r in range(n_rounds):
            bs = [batch_at(r * args.lm_tau + i) for i in range(args.lm_tau)]
            stacked = {k: jnp.stack([St.route_batch(b)[k] for b in bs])
                       for k in bs[0] if k != "route"}
            params, opt_state, loss = rnd(params, opt_state, stacked)
            losses.append(float(loss))
            print(f"round {r:3d} (tau={args.lm_tau}) loss {losses[-1]:.4f}",
                  flush=True)
    else:
        mesh = jax.make_mesh((shards, n_dev // shards), ("data", "model"))
        step_fn = St.make_train_step(cfg, opt)
        with mesh:
            jstep = jax.jit(step_fn, donate_argnums=(0, 1))
            for it in range(args.steps):
                params, opt_state, m = jstep(params, opt_state, batch_at(it))
                losses.append(float(m["loss"]))
                if it % max(args.steps // 10, 1) == 0:
                    print(f"step {it:4d} loss {losses[-1]:.4f}", flush=True)
    dt = time.time() - t0
    out = {"mode": "lm", "arch": args.arch, "loss_first": losses[0],
           "loss_last": float(np.mean(losses[-5:])),
           "steps_per_s": args.steps / dt,
           "moved_frac": float((plan.s * (1 - np.eye(shards))).sum()  # foglint: disable=dense-materialization -- shard-count square (≤ 8), not the device axis
                               / plan.s.shape[0] / shards)}
    print(json.dumps(out, indent=2))
    return out


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["fog", "lm"], default="fog")
    ap.add_argument("--seed", type=int, default=0)
    # fog
    ap.add_argument("--model", default="cnn")
    ap.add_argument("--n", type=int, default=10)
    ap.add_argument("--T", type=int, default=100)
    ap.add_argument("--tau", type=int, default=10)
    ap.add_argument("--eta", type=float, default=0.1)
    ap.add_argument("--n-train", type=int, default=20000)
    ap.add_argument("--n-test", type=int, default=4000)
    ap.add_argument("--topology", default="full")
    ap.add_argument("--rho", type=float, default=1.0)
    ap.add_argument("--setting", default="B", choices=list("ABCDE"))
    ap.add_argument("--costs", default="testbed", choices=["testbed",
                                                           "synthetic"])
    ap.add_argument("--error-model", default="discard",
                    choices=["discard", "neg_G", "sqrt"])
    ap.add_argument("--f-err", type=float, default=0.7)
    ap.add_argument("--non-iid", action="store_true")
    ap.add_argument("--p-exit", type=float, default=0.0)
    ap.add_argument("--p-entry", type=float, default=0.0)
    ap.add_argument("--schedule", default="static",
                    choices=["static", "churn", "flap"],
                    help="network schedule: static, node entry/exit "
                         "churn (ChurnProcess producer; the movement "
                         "plane sees inactive endpoints), or seeded "
                         "link flaps")
    ap.add_argument("--churn", type=float, default=0.0,
                    help="shorthand: --schedule churn with "
                         "p_exit = p_entry = CHURN")
    ap.add_argument("--p-flap", type=float, default=0.05,
                    help="per-round link failure prob (--schedule flap)")
    ap.add_argument("--p-recover", type=float, default=0.5,
                    help="per-round failed-link recovery prob")
    ap.add_argument("--replan", default="oracle",
                    choices=["oracle", "predict", "once"],
                    help="what the planner sees under a dynamic "
                         "schedule: the true schedule (oracle, "
                         "replan-on-event), the schedule predicted "
                         "from the observed event history "
                         "(estimator.predict_schedule — deployable "
                         "setting-C style), or the static base graph "
                         "(once). Execution always runs on truth")
    ap.add_argument("--plan-once", action="store_true",
                    help="alias for --replan once (plan on the base "
                         "graph; realization loses in-flight data over "
                         "dead links / churned-out receivers)")
    ap.add_argument("--tiers", default=None, metavar="SPEC",
                    help="hierarchical aggregation tree as "
                         "'g1@tau1,g2@tau2,...' (e.g. '4@10,1@20': 4 "
                         "gateways every 10 rounds, one root every "
                         "20); the first period must equal --tau and "
                         "the last group count must be 1")
    ap.add_argument("--engine", default="auto",
                    choices=["auto", "scan", "sharded", "legacy"],
                    help="fog training engine: one compiled scan "
                         "(--tiers selects its tiered program), the "
                         "device-sharded scan (shard_map over a 'data' "
                         "mesh; auto picks it on multi-device hosts), "
                         "or the legacy per-round oracle loop")
    ap.add_argument("--faults", default="none",
                    choices=["none", "straggle", "drop", "crash",
                             "corrupt", "mixed"],
                    help="unannounced fault injection (core.faults): "
                         "straggler upload misses, dropped uploads, "
                         "crash-mid-window exits, corrupted updates, "
                         "or an even mix — sampled per window at "
                         "--fault-rate from a separate seeded stream")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="per-upload (per-window for crash) fault "
                         "probability; 0 disables injection")
    ap.add_argument("--corrupt-mode", default="nan",
                    choices=["nan", "inf", "scale"],
                    help="corrupted-update payload: non-finite (caught "
                         "by the finite-masking guard) or a Byzantine "
                         "scale that survives it")
    ap.add_argument("--quorum", type=float, default=0.0,
                    help="minimum surviving-upload fraction for a "
                         "window's aggregation to commit; below it the "
                         "previous global carries forward")
    ap.add_argument("--unguarded", action="store_true",
                    help="disable guarded aggregation (finite-masking "
                         "+ survivor renormalization) — the ablation "
                         "arm of the fault_tolerance bench")
    ap.add_argument("--checkpoint", default=None, metavar="PATH",
                    help="snapshot training state atomically at every "
                         "aggregation-window boundary (scan engine)")
    ap.add_argument("--resume", default=None, metavar="CKPT",
                    help="continue a --checkpoint snapshot mid-horizon "
                         "(bitwise-equal on CPU to an uninterrupted "
                         "run)")
    ap.add_argument("--sanitize", action="store_true",
                    help="runtime sanitizer smoke: run the scenario "
                         "cold then warm under debug_nans + "
                         "transfer_guard('disallow') around the hot "
                         "loop, raising if the warm pass recompiles "
                         "(small-n checks, not a benchmark mode)")
    # lm
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--data-shards", type=int, default=1)
    ap.add_argument("--lm-tau", type=int, default=1,
                    help="FedAvg local steps per aggregation (lm mode)")
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args(argv)
    return run_fog(args) if args.mode == "fog" else run_lm(args)


if __name__ == "__main__":
    main()
