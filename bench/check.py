"""The comparison that decides ``correct``: the numbers a run compares,
each against its limit in ``bench/limits/<workload>.json``.

Every number is worked out from what the timed path produced for one
call and from the plain reference (``reference.py``) on that call's
inputs, which ``gen.py`` draws again from (seed, call index):

* ``greedy_wrong``: Theorem 3 decisions of the program's planner that
  differ from the float64 rule by more than float32 resolution;
* ``repair_gap``: the largest gap between a share of the program's
  repaired plan and the reference repair of the program's greedy plan;
* ``plane_wrong``: samples routed other than the plan says (per source
  cell and destination cell), samples processed twice, and processed
  counts, H weights or aggregation rounds in the history that differ
  from the routed data;
* ``first_loss_gap``: the largest gap of a device's loss in the first
  round, from the initial weights, relative as below;
* ``loss_gap``: the largest gap of a device's loss in the first
  aggregation window and in the round after it, relative to the
  reference's loss or to the median reference loss, whichever is larger;
* ``broadcast_loss_gap``: the same over the round after the first
  aggregation alone, which every device starts from the aggregated model;
* ``test_loss_gap``: the relative gap of the test loss after the first
  aggregation.

The data plane and the training are checked on the plan the program
made, once that plan has been checked against the rule.
"""
from __future__ import annotations

import numpy as np

import reference as ref


def plan_arrays(t, src, dst, qty, r, T, n):
    """Dense (s (T, n, n), r) of a plan given as COO edges."""
    s = np.zeros((T, n, n))
    np.add.at(s, (t, src, dst), qty)
    return s, np.asarray(r, np.float64)


def planner_numbers(call, adj, setting, cap, out):
    """greedy_wrong, and repair_gap where the setting repairs."""
    c = call.costs
    dec_ref = ref.greedy_rule(c.c_node, c.c_link, c.f_err, adj)
    nums = {"greedy_wrong": ref.wrong_decisions(
        c.c_node, c.c_link, c.f_err, out["greedy_dec"], dec_ref)}
    if setting == "D":
        s0, r0 = ref.decisions_to_plan(out["greedy_dec"])
        s_ref, r_ref = ref.repair(s0, r0, c.c_node, c.f_err, cap, adj, call.D)
        T, n = call.D.shape
        s, r = plan_arrays(*out["plan"], T, n)
        nums["repair_gap"] = float(max(np.abs(s - s_ref).max(),
                                       np.abs(r - r_ref).max()))
    return nums


def plane_wrong(call, out, tau):
    """Routing, double processing and history mismatches of one job."""
    T, n = call.D.shape
    s, r = plan_arrays(*out["plan"], T, n)
    split = ref.split_counts(s, r, call.D)
    # reference: samples moved from source cell (t, i) to each destination
    t, i, j = np.nonzero(split)
    dest_t = np.where(i == j, t, t + 1)
    keep = dest_t < T
    want = {}
    for a, b, c, v in zip(t[keep] * n + i[keep], dest_t[keep], j[keep],
                          split[t, i, j][keep]):
        want[(int(a), int(b) * n + int(c))] = int(v)
    # program: the source cell of every processed sample
    size = 1 + max((int(ix.max()) for row in call.cells for ix in row
                    if len(ix)), default=0)
    src_of = np.full(size, -1, np.int64)
    for tt, row in enumerate(call.cells):
        for ii, ix in enumerate(row):
            src_of[ix] = tt * n + ii
    got, seen, bad = {}, [], 0
    processed = out["processed"]
    for tt, row in enumerate(processed):
        for jj, ix in enumerate(row):
            if not len(ix):
                continue
            seen.append(ix)
            srcs = src_of[np.minimum(ix, size - 1)]
            bad += int(((ix >= size) | (srcs < 0)).sum())
            for a, v in zip(*np.unique(srcs[srcs >= 0], return_counts=True)):
                got[(int(a), tt * n + jj)] = int(v)
    ids = np.concatenate(seen) if seen else np.empty(0, np.int64)
    wrong = bad + int(ids.size - np.unique(ids).size)
    wrong += sum(want.get(k, 0) != got.get(k, 0) for k in set(want) | set(got))
    # the history the engine returned
    hist = out["hist"]
    counts = np.array([[len(ix) for ix in row] for row in processed],
                      np.float64)
    wrong += int((np.asarray(hist["processed_counts"], np.float64)
                  != counts).sum())
    windows = T // tau
    agg = [w * tau + tau - 1 for w in range(windows)]
    wrong += int(list(hist["agg_round"]) != agg)
    H = counts[:windows * tau].reshape(windows, tau, n).sum(1)
    H_prog = np.asarray(hist["H_agg"], np.float64)
    wrong += int(H_prog.shape != H.shape) or int((H_prog != H).sum())
    return wrong


def reference_window(call, out, config, model, data):
    """The reference's (losses (τ + 1, n), test loss) of one job, trained
    on the samples the program processed; ``model`` is the
    configuration's model module."""
    tau = int(config["tau"])
    return ref.first_window(model, config, call.seed, data,
                            out["processed"][:tau + 1],
                            precision=config["matmul_precision"])


def window_gaps(prog, losses):
    """(τ + 1, n) gaps of the program's losses, relative to the
    reference's loss or the median reference loss, whichever is larger."""
    floor = max(float(np.median(np.abs(losses))), 1e-12)
    return np.abs(prog - losses) / np.maximum(np.abs(losses), floor)


def training_numbers(call, out, config, model, data, *, model_out=None,
                     reference=None):
    """first_loss_gap, loss_gap, broadcast_loss_gap and test_loss_gap of
    one job against the reference.

    ``model_out`` stands in for the program's (losses, test_loss) when a
    control puts another computation in the program's place;
    ``reference`` is the reference's, when already worked out."""
    tau = int(config["tau"])
    losses, test_loss = reference or reference_window(call, out, config,
                                                      model, data)
    if model_out is None:
        hist = out["hist"]
        prog = np.stack([np.asarray(v, np.float64)
                         for v in hist["device_loss"][:tau + 1]])
        prog_test = float(hist["test_loss"][0])
    else:
        prog, prog_test = model_out
    gap = window_gaps(prog, losses)
    return {"first_loss_gap": float(gap[0].max()),
            "loss_gap": float(gap.max()),
            "broadcast_loss_gap": float(gap[tau].max()),
            "test_loss_gap": abs(prog_test - test_loss) / abs(test_loss)}


def call_numbers(call, out, config, model, adj, data, reference=None):
    """Every number one job compares."""
    nums = planner_numbers(call, adj, config["setting"], float(call.D.mean()),
                           out)
    nums["plane_wrong"] = plane_wrong(call, out, int(config["tau"]))
    nums.update(training_numbers(call, out, config, model, data,
                                 reference=reference))
    return nums


def judge(per_call, limits):
    """Worst reading of each number over the checked calls, beside its
    limit, and whether every reading is within its limit."""
    worst = {}
    for nums in per_call:
        for k, v in nums.items():
            worst[k] = max(worst.get(k, v), v)
    missing = set(worst) - set(limits)
    if missing:
        raise KeyError(f"no limit for {sorted(missing)}")
    failed = sum(any(v > limits[k] for k, v in nums.items())
                 for nums in per_call)
    rows = {k: {"value": worst[k], "limit": limits[k]} for k in sorted(worst)}
    return failed == 0 and bool(per_call), failed, rows
