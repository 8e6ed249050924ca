"""Time the scan engine's program at the paper's CNN cell for each chunk
size C of the packed local-SGD rows, and for the dense (T, n, P) slots.

    PYTHONPATH=src python scripts/packed_chunk_sweep.py [--jobs 4] \
        [--chunks 32,64,128] [--seed 2147483659] [--small] \
        [--precision default|highest]

Jobs are drawn by the benchmark's generator (``bench/gen.py``) for the
``paper_cnn_n10`` configuration and planned and routed once; each
variant then trains every job through ``run_network_aware(engine=
"scan")`` after one warm-up job that compiles it. The dense variant sets
the chunk so large that the packed rows can never execute fewer slots.
Per variant the last stdout lines give, as JSON: the slots executed a
job, the ``train.device`` span per job in ms (the program from dispatch
to ``block_until_ready``), the warm-up's seconds, and per job the
largest gap of a device loss from the dense variant's in each round of
the first window and the round after it (round τ starts from the first
aggregate), and the gap of the first test loss, each relative as the
benchmark's check computes it (``bench/check.window_gaps``). ``--precision`` sets JAX's
default matmul precision: at ``highest`` the variants differ by their
summation order alone. ``--small`` shrinks the configuration to a CPU
rehearsal.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))
sys.path.insert(0, os.path.join(ROOT, "src"))

DENSE = 1 << 20


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--chunks", default="32,64,128")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--precision", default="default",
                    choices=["default", "highest"])
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    jax.config.update("jax_default_matmul_precision", args.precision)
    import check
    import gen
    import run
    from repro.core import federated as F
    from repro.core import monitoring
    from repro.data import pipeline as pl

    config = run.load_json(os.path.join(ROOT, "bench", "configs",
                                        "paper_cnn_n10.json"))
    traffic = run.load_json(os.path.join(ROOT, "bench", "traffic",
                                         "epoch.json"))
    if args.small:
        config.update(n=4, T=4, tau=2, n_train=800, n_test=200,
                      max_points=256)
    data = gen.image_dataset(int(config["n_train"]), int(config["n_test"]),
                             int(config["data_seed"]))
    sut = run.System(config, data)
    traffic_gen = gen.Traffic(config, traffic)
    jobs = []
    for k in range(args.jobs + 1):
        call = traffic_gen.call(args.seed, k)
        traces, streams, cfg = sut.inputs(call)
        plan = sut.mv.greedy_linear(traces, sut.adj)
        prep = F._prepare_streams(cfg, data, plan, streams, None, None)
        jobs.append((cfg, traces, plan, prep))

    def train(job):
        cfg, traces, plan, prep = job
        monitoring.reset()
        hist = F.run_network_aware(cfg, data, traces, None, plan,
                                   prepared=prep, engine="scan")
        tot = monitoring.totals()
        return hist, tot["train.device"]["seconds"], tot["train.stage"]

    tau = int(config["tau"])
    rows, dense = [], None
    for C in [DENSE] + [int(c) for c in args.chunks.split(",")]:
        pl.PACKED_CHUNK = C
        t0 = time.perf_counter()
        train(jobs[0])
        warm_s = time.perf_counter() - t0
        ms, slots, packed, window = [], [], [], []
        for job in jobs[1:]:
            hist, dev_s, st = train(job)
            ms.append(1e3 * dev_s)
            slots.append(int(st["slots"]))
            packed.append(int(st["packed"]))
            window.append((np.stack(hist["device_loss"][:tau + 1]),
                           float(hist["test_loss"][0])))
        if dense is None:
            dense = window
        gaps = [check.window_gaps(a, b) for (a, _), (b, _)
                in zip(window, dense)]
        rows.append({"chunk": "dense" if C == DENSE else C,
                     "precision": args.precision,
                     "packed": packed, "slots": slots,
                     "device_ms": ms,
                     "device_ms_median": statistics.median(ms),
                     "warmup_s": warm_s,
                     "loss_gap_by_round": [g.max(1).tolist() for g in gaps],
                     "test_loss_gap": [abs(a - b) / abs(b) for (_, a), (_, b)
                                       in zip(window, dense)]})
        print(json.dumps(rows[-1]), flush=True)
    return rows


if __name__ == "__main__":
    main()
