"""Readings that set the limits of a cell's correctness check.

    python bench/control.py --workload <cell> --seeds 11,12,13 --calls 3

For every seed it drives ``--calls`` calls of the cell through the
system under test, exactly as a run does, and reads each number the
check compares three ways:

* ``program``: the system under test against the reference (the lower
  reading of a limit comes from these);
* ``control``: the reference put in the program's place one precision
  below the configuration's: float32 becomes bfloat16 (the planner's
  costs, the model's weights, floating inputs and activations), at the
  configuration's matmul precision;
* the faults the check must catch, planted in the reference put in the
  program's place: ``unchanged`` (a step returns the weights it got),
  ``half_batch`` (half of every batch left out, the mean over the rest),
  ``no_broadcast`` (the devices keep their own models after eq. (4)),
  ``answer`` (one planner decision, or one processed sample's device,
  altered where it is produced).

One JSON line per seed gives the worst reading of each number over its
calls; the last line gives, per number, the largest program reading and
the smallest reading of the control and of each fault. It needs a TPU,
like the benchmark; the benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import ml_dtypes
import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import check  # noqa: E402
import gen  # noqa: E402
import reference as ref  # noqa: E402
import run  # noqa: E402


def altered(dec, seed):
    """One decision moved to another device (or from discard to local)."""
    dec = dec.copy()
    rng = np.random.default_rng(seed)
    T, n = dec.shape
    t, i = int(rng.integers(0, T)), int(rng.integers(0, n))
    dec[t, i] = i if dec[t, i] != i else (i + 1) % n
    return dec


def plan_edges(s, r):
    """COO edges (t, src, dst, qty, r) of a dense plan."""
    t, i, j = np.nonzero(s)
    return (t, i, j, np.asarray(s[t, i, j], np.float64),
            np.asarray(r, np.float64))


def moved_sample(processed, seed):
    """One processed sample handed to another device of its round."""
    rng = np.random.default_rng(seed)
    cells = [(t, j) for t, row in enumerate(processed)
             for j, ix in enumerate(row) if len(ix)]
    t, j = cells[int(rng.integers(0, len(cells)))]
    row = [ix.copy() for ix in processed[t]]
    dst = (j + 1) % len(row)
    row[dst] = np.concatenate([row[dst], row[j][:1]])
    row[j] = row[j][1:]
    return processed[:t] + [row] + processed[t + 1:]


def readings(call, out, config, model, adj, data, rounds_gap=None):
    """{variant: {number: reading}} of one call. ``rounds_gap``, where
    given, collects {variant: largest loss gap of each round}."""
    reference = check.reference_window(call, out, config, model, data)
    res = {"program": check.call_numbers(call, out, config, model, adj, data,
                                         reference=reference)}
    c = call.costs
    bf16 = ml_dtypes.bfloat16
    dec64 = ref.greedy_rule(c.c_node, c.c_link, c.f_err, adj)
    dec16 = ref.greedy_rule(c.c_node, c.c_link, c.f_err, adj, dtype=bf16)
    res["control"] = {"greedy_wrong": ref.wrong_decisions(
        c.c_node, c.c_link, c.f_err, dec16, dec64)}
    res["answer"] = {"greedy_wrong": ref.wrong_decisions(
        c.c_node, c.c_link, c.f_err, altered(dec64, call.seed), dec64)}
    if config["setting"] == "D":
        s0, r0 = ref.decisions_to_plan(out["greedy_dec"])
        s32, r32 = ref.repair(s0, r0, c.c_node, c.f_err, float(call.D.mean()),
                              adj, call.D, dtype=np.float32)
        ctl = dict(out, plan=plan_edges(s32, r32))
        res["control"]["repair_gap"] = check.planner_numbers(
            call, adj, "D", float(call.D.mean()), ctl)["repair_gap"]
    res["answer"]["plane_wrong"] = check.plane_wrong(
        call, dict(out, processed=moved_sample(out["processed"], call.seed)),
        int(config["tau"]))
    tau = int(config["tau"])
    rounds = out["processed"][:tau + 1]
    variants = {"control": dict(dtype="bfloat16"),
                "unchanged": dict(fault="unchanged"),
                "half_batch": dict(fault="half_batch"),
                "no_broadcast": dict(fault="no_broadcast")}
    outs = {"program": (np.stack([np.asarray(v, np.float64) for v in
                                  out["hist"]["device_loss"][:tau + 1]]),
                        None)}
    for name, kw in variants.items():
        mo = ref.first_window(model, config, call.seed, data, rounds,
                              precision=config["matmul_precision"], **kw)
        outs[name] = mo
        res.setdefault(name, {}).update(check.training_numbers(
            call, out, config, model, data, model_out=mo,
            reference=reference))
    if rounds_gap is not None:
        for name, (losses, _) in outs.items():
            g = check.window_gaps(losses, reference[0]).max(axis=1)
            prev = rounds_gap.get(name, np.zeros_like(g))
            rounds_gap[name] = np.maximum(prev, g)
    return res


def main(argv=None, *, require_tpu: bool = True, root: str = ROOT):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--calls", type=int, default=3)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload, root)
    config, traffic, model = cell["config"], cell["traffic"], cell["model"]
    sys.path.insert(0, os.path.join(root, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    run.find_devices(int(cell["workload"]["chips"]), require_tpu)
    data = model.dataset(config)
    tg = gen.Traffic(config, traffic)
    sut = run.System(config, data, model)
    spans = run.Spans(annotate=False)
    worst = {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        per_seed, rounds_gap = {}, {}
        for k in range(args.calls):
            call = tg.call(seed, k)
            out = sut.run(call, spans)
            for variant, nums in readings(call, out, config, model, sut.adj,
                                          data, rounds_gap).items():
                d = per_seed.setdefault(variant, {})
                for name, v in nums.items():
                    d[name] = max(d.get(name, v), v)
        print(json.dumps({"seed": seed, **per_seed,
                          "rounds_gap": {k: [float(x) for x in v]
                                         for k, v in rounds_gap.items()}}),
              flush=True)
        for variant, nums in per_seed.items():
            d = worst.setdefault(variant, {})
            pick = max if variant == "program" else min
            for name, v in nums.items():
                d[name] = pick(d.get(name, v), v)
    print(json.dumps({"workload": args.workload, "summary": worst}),
          flush=True)


if __name__ == "__main__":
    main()
