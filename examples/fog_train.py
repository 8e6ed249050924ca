"""End-to-end driver: the paper's experiment at full fidelity — CNN over a
fog network, testbed-like costs, non-iid data, capacity constraints and
imperfect information (setting E), with the Table-III cost decomposition.

    PYTHONPATH=src python examples/fog_train.py [--full]

--full restores paper scale (n=10, T=100, tau=10, 60k images); default is
a few minutes on CPU.

Engine / mesh knobs
-------------------
``--engine`` selects the training engine (default "auto"):

* ``scan``    — the whole horizon as one compiled ``jax.lax.scan`` on a
  single device;
* ``sharded`` — the same scan partitioned across every visible device
  via ``shard_map`` over a 1-D "data" mesh
  (``repro.launch.mesh.make_data_mesh``): the n fog devices are padded
  to a mesh multiple with phantom inactive devices, the every-τ
  H-weighted aggregation runs as a cross-shard ``psum``, and test
  evaluation is streamed off the hot path by the engine's
  AsyncEvaluator. ``auto`` picks this whenever more than one device is
  visible — force a multi-device CPU mesh with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``;
* ``legacy``  — the original per-round loop (numerical oracle).

Sweeps batch many runs into one compiled program — sharded on
multi-device hosts — via ``benchmarks.fog.run_scenarios``.

Programmatic callers can pass an explicit mesh:
``run_network_aware(..., engine="sharded", mesh=make_data_mesh(4))``.

Network dynamics knobs
----------------------
``--churn 0.05`` runs the paper's §V-E entry/exit dynamics (p_exit =
p_entry = 0.05) through the NetworkSchedule plane: planning replans on
every event (the movement plane sees inactive endpoints), the engine
stages the same active mask. ``--schedule flap`` flips links instead.
``--replan`` picks what the planner sees: ``oracle`` (the true
schedule, replan-on-event), ``predict`` (the schedule estimated from
the observed event history — window-averaged link-availability and
device-activity rates, the deployable setting-C analog), or ``once``
(the static base graph; ``--plan-once`` is an alias). Execution always
runs on the true schedule — predictive and plan-once plans are
realized against it, losing data in flight over dead links or toward
receivers that churned out by the arrival round.

Fault-injection knobs
---------------------
``--faults corrupt --fault-rate 0.1`` injects UNANNOUNCED failures the
planner never sees (``repro.core.faults``): straggler upload misses,
dropped uploads, crash-mid-window exits, corrupted (NaN/Inf or
Byzantine-scaled) updates, or an even ``mixed`` blend. The engine
survives them through guarded aggregation (finite-masking + survivor
renormalization; ``--unguarded`` ablates it) and a ``--quorum``
fraction below which a window's aggregation is skipped and the
previous global carries forward.
"""
import argparse
import json

from repro.launch.train import main as train_main

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--setting", default="B", choices=list("ABCDE"))
    ap.add_argument("--non-iid", action="store_true")
    ap.add_argument("--engine", default="auto",
                    choices=["auto", "scan", "sharded", "legacy"])
    ap.add_argument("--schedule", default="static",
                    choices=["static", "churn", "flap"])
    ap.add_argument("--churn", type=float, default=0.0)
    ap.add_argument("--replan", default="oracle",
                    choices=["oracle", "predict", "once"])
    ap.add_argument("--plan-once", action="store_true")
    ap.add_argument("--faults", default="none",
                    choices=["none", "straggle", "drop", "crash",
                             "corrupt", "mixed"])
    ap.add_argument("--fault-rate", type=float, default=0.0)
    ap.add_argument("--quorum", type=float, default=0.0)
    ap.add_argument("--unguarded", action="store_true")
    args = ap.parse_args()
    argv = ["--mode", "fog", "--model", "cnn", "--setting", args.setting,
            "--costs", "testbed", "--engine", args.engine,
            "--schedule", args.schedule, "--replan", args.replan,
            "--faults", args.faults, "--fault-rate", str(args.fault_rate),
            "--quorum", str(args.quorum)]
    if args.churn:
        argv += ["--churn", str(args.churn)]
    if args.plan_once:
        argv.append("--plan-once")
    if args.unguarded:
        argv.append("--unguarded")
    if args.non_iid:
        argv.append("--non-iid")
    if args.full:
        argv += ["--n", "10", "--T", "100", "--tau", "10",
                 "--n-train", "60000", "--n-test", "10000"]
    else:
        argv += ["--n", "8", "--T", "40", "--tau", "5",
                 "--n-train", "20000", "--n-test", "4000"]
    train_main(argv)
