"""Chip smoke test: drive the fog-learning main path once on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the sharded engine only

One process does everything (a chip belongs to one process at a time).
Each phase prints one line of findings; the last line of standard output
is ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU, or when any phase fails, the script exits non-zero and
prints no such line.

One-chip phases:

* device      JAX version and devices; refuses anything but a TPU.
* paper_cell  the paper's CNN cell (n=10, T=100, τ=10, η=0.1, 60k/10k
              synthetic images, testbed costs, setting B, full topology)
              through ``launch.train.main`` on the scan engine, cold and
              warm, and on the per-round ``legacy`` reference.
* planner     ``movement.greedy_linear`` with the Pallas min-plus kernel
              against the f64 numpy rule (n=1024, T=50, full topology).
* segments    the segment-reduce kernel against ``jax.ops`` at the shapes
              ``pipeline.counts_flat`` and ``engine.aggregate_tier`` stage.

``--chips 4`` runs the paper cell on the sharded engine over a 4-chip
"data" mesh and compares it with the scan engine on one chip.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# The paper cell: paper §V-A settings at full width (FULL scale of
# benchmarks/fog.py), seed 0.
PAPER_ARGV = ["--mode", "fog", "--model", "cnn", "--n", "10", "--T", "100",
              "--tau", "10", "--eta", "0.1", "--n-train", "60000",
              "--n-test", "10000", "--topology", "full", "--setting", "B",
              "--costs", "testbed", "--seed", "0"]
# How two runs of the cell are compared. Runs that differ only in
# rounding (another backend, or an engine that sums in another order,
# as the sharded engine's per-shard sums and psum do against the scan
# engine's einsum) split at the last bit, and η=0.1 SGD on this CNN
# amplifies that: from the second eval on, their accuracy curves drift
# apart by up to 0.10 while all of them still train. So the comparison
# is tight where rounding cannot have grown yet, and loose only at the
# end:
# * the aggregation schedule and H weights come from the data plane and
#   must be equal;
# * the first window's per-device losses (local SGD before any
#   aggregation) and the test loss after the first aggregation must
#   agree to WINDOW0_LOSS_RTOL, about 30 times the 2.4e-5 and 3.6e-5
#   that sharded and scan differ by on v5e chips;
# * the final accuracies may differ by FINAL_ACC_TOL, about 5 times the
#   spread of the rounding-only runs: legacy on the CPU ends at 0.9173,
#   scan and legacy on one v5e at 0.9151, sharded on four at 0.9213.
WINDOW0_LOSS_RTOL = 1e-3
FINAL_ACC_TOL = 0.03
# Final test accuracy every run must reach: the CPU run of the same
# command with --engine legacy ends at 0.9173, and a run may end
# FINAL_ACC_TOL from another.
ACC_FLOOR = 0.887

PLANNER_N, PLANNER_T = 1024, 50
# A decision may differ from the f64 rule only where the two candidates'
# f64 costs are closer than f32 resolution: each candidate is a sum of
# at most two f32-rounded costs, so 4 ulps of the larger magnitude.
F32_EPS = 2.0 ** -23

# pipeline.counts_flat at the sparse_scale CI point (n=10,240, T=50,
# one sample per device-round on average)
FLAT_N, FLAT_T = 10_240, 50
# engine.aggregate_tier over a (4096, P) stack of the linear model
TIER_M, TIER_GROUPS = 4096, 64


class SmokeFailure(RuntimeError):
    pass


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=float), flush=True)


def device_phase(chips: int):
    import jax

    devs = jax.devices()
    info = {"jax": jax.__version__, "platform": devs[0].platform,
            "kind": devs[0].device_kind, "count": len(devs)}
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found ({info}); refusing to run "
                 "on another backend")
    check(len(devs) >= chips, f"--chips {chips} needs {chips} devices, "
          f"found {len(devs)}")
    emit("device", **info, devices=[str(d) for d in devs])
    return devs


@contextlib.contextmanager
def _histories():
    """Collect the histories ``run_network_aware`` hands the CLI, whose
    report keeps only the accuracy curve."""
    from repro.core import federated as F

    run, seen = F.run_network_aware, []

    def recording(*args, **kw):
        seen.append(run(*args, **kw))
        return seen[-1]

    F.run_network_aware = recording
    try:
        yield seen
    finally:
        F.run_network_aware = run


def run_cell(engine: str, argv=PAPER_ARGV):
    """One paper-cell run through the CLI entry point; its JSON report
    is kept off stdout so each phase stays one line. Returns the
    summary to print and the run's history."""
    from repro.core import monitoring
    from repro.launch import train

    c0 = monitoring.compile_events()
    t0 = time.perf_counter()
    with _histories() as hists, contextlib.redirect_stdout(io.StringIO()):
        out = train.main(list(argv) + ["--engine", engine])
    wall = time.perf_counter() - t0
    curve = [float(a) for a in out["acc_curve"]]
    check(out["engine"] == engine, f"asked for {engine}, ran "
          f"{out['engine']}")
    check(len(hists) == 1, f"{len(hists)} training runs in one cell")
    return {"engine": engine, "acc_curve": curve, "final_acc": curve[-1],
            "compiles": monitoring.compile_events() - c0,
            "wall_s_per_window_informational": wall / len(curve)}, hists[0]


def compare_runs(phase: str, a: dict, ha: dict, b: dict, hb: dict,
                 floor=ACC_FLOOR, **extra) -> None:
    """The checks of the comment on WINDOW0_LOSS_RTOL, between run a and
    the reference run b: prints their numbers, then fails on a breach."""
    import numpy as np

    check(len(a["acc_curve"]) == len(b["acc_curve"]),
          "accuracy curves differ in length")
    w0 = hb["agg_round"][0] + 1            # rounds up to the first eq. (4)
    la = np.stack(ha["device_loss"][:w0])
    lb = np.stack(hb["device_loss"][:w0])
    dev_gap = float((np.abs(la - lb) / np.maximum(np.abs(lb), 1e-6)).max())
    test_gap = abs(ha["test_loss"][0] - hb["test_loss"][0]) \
        / abs(hb["test_loss"][0])
    same_schedule = (ha["agg_round"] == hb["agg_round"]
                     and np.array_equal(np.stack(ha["H_agg"]),
                                        np.stack(hb["H_agg"])))
    final_gap = abs(a["final_acc"] - b["final_acc"])
    emit(phase, run=f"compare_{a['engine']}_{b['engine']}", **extra,
         same_schedule_and_H=same_schedule,
         window0_device_loss_rel_gap=dev_gap,
         window0_test_loss_rel_gap=test_gap, loss_rtol=WINDOW0_LOSS_RTOL,
         final_acc_gap=final_gap, final_acc_tol=FINAL_ACC_TOL, floor=floor,
         max_acc_gap_informational=max(
             abs(x - y) for x, y in zip(a["acc_curve"], b["acc_curve"])))
    check(same_schedule, f"{a['engine']} and {b['engine']} aggregate on "
          "different rounds or with different H weights")
    check(dev_gap <= WINDOW0_LOSS_RTOL, f"first-window device losses "
          f"differ by {dev_gap} relative")
    check(test_gap <= WINDOW0_LOSS_RTOL, f"first test loss differs by "
          f"{test_gap} relative")
    check(final_gap <= FINAL_ACC_TOL, f"final accuracies differ by "
          f"{final_gap}")
    for r in (a, b):
        check(r["final_acc"] >= floor, f"{r['engine']} final accuracy "
              f"{r['final_acc']} below the floor {floor}")


def paper_cell_phase(argv=PAPER_ARGV, floor=ACC_FLOOR):
    cold, h_cold = run_cell("scan", argv)
    emit("paper_cell", run="scan_cold", **cold)
    warm, _ = run_cell("scan", argv)
    emit("paper_cell", run="scan_warm", **warm)
    legacy, h_legacy = run_cell("legacy", argv)
    emit("paper_cell", run="legacy", **legacy)
    compare_runs("paper_cell", cold, h_cold, legacy, h_legacy, floor,
                 warm_equals_cold=cold["acc_curve"] == warm["acc_curve"])


def _decisions(plan, T: int, n: int):
    """(T, n) per-(t, i) decision: -1 discard, else the processing
    device (i itself, or the offload target)."""
    import numpy as np

    dec = np.full((T, n), -1, np.int64)
    e = plan.edges
    dec[e.t, e.src] = e.dst
    return dec


def _decision_cost(tr, dec):
    """f64 cost of each (t, i) decision under the traces."""
    import numpy as np

    T, n = dec.shape
    t, i = np.meshgrid(np.arange(T), np.arange(n), indexing="ij")
    c_next = np.concatenate([tr.c_node[1:], tr.c_node[-1:]])
    j = np.maximum(dec, 0)
    off = tr.c_link[t, i, j] + c_next[t, j]
    return np.where(dec < 0, tr.f_err, np.where(dec == i, tr.c_node, off))


def planner_phase(n=PLANNER_N, T=PLANNER_T):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import movement as mv
    from repro.core.costs import synthetic_costs
    from repro.core.topology import fully_connected
    from repro.kernels import ops

    tr = synthetic_costs(n, T, np.random.default_rng(0))
    adj = fully_connected(n)
    t0 = time.perf_counter()
    plan_p = mv.greedy_linear(tr, adj, backend="pallas")
    pallas_s = time.perf_counter() - t0
    plan_n = mv.greedy_linear(tr, adj, backend="numpy")
    f32 = jax.ShapeDtypeStruct((T, n), jnp.float32)
    text = ops.greedy_edges_batched.lower(
        jax.ShapeDtypeStruct((T, n, n), jnp.float32), f32, f32, f32,
        jax.ShapeDtypeStruct((T, n, n), jnp.bool_),
        use_pallas=True).compile().as_text()
    dec_p, dec_n = _decisions(plan_p, T, n), _decisions(plan_n, T, n)
    diff = dec_p != dec_n
    cp, cn = _decision_cost(tr, dec_p), _decision_cost(tr, dec_n)
    gap = np.abs(cp - cn)[diff]
    tol = (4 * F32_EPS * np.maximum(np.abs(cp), np.abs(cn)))[diff]
    emit("planner", n=n, T=T, tpu_custom_call="tpu_custom_call" in text,
         decisions=T * n, differing=int(diff.sum()),
         max_f64_gap_where_differing=float(gap.max()) if gap.size else 0.0,
         all_within_f32_resolution=bool((gap <= tol).all()),
         pallas_wall_s_informational=pallas_s)
    check("tpu_custom_call" in text, "greedy kernel ran without Mosaic")
    check((gap <= tol).all(), f"{int((gap > tol).sum())} decisions differ "
          "by more than f32 resolution")


def _kernel_program(fn, data, ids, S: int) -> str:
    return fn.lower(data, ids, num_segments=S,
                    use_pallas=True).compile().as_text()


def segments_phase(flat_n=FLAT_N, flat_t=FLAT_T, tier_m=TIER_M,
                   groups=TIER_GROUPS):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import engine as eng
    from repro.data import pipeline as pl
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    # counts_flat: cells of a flat Poisson stream; the sums are integer
    # counts, exact in f32, and a max is exact — both must match bitwise
    y = rng.integers(0, 10, 4096)
    flat = pl.poisson_streams_flat(flat_n, flat_t, y,
                                   rng=np.random.default_rng(3),
                                   mean_per_round=1.0)
    ids = jnp.asarray(flat.cell_key().astype(np.int32))
    S = flat_t * flat_n
    E = int(ids.shape[0])
    ones = jnp.ones(E, jnp.float32)
    vals = jnp.asarray(rng.standard_normal(E).astype(np.float32))
    t0 = time.perf_counter()
    cnt = np.asarray(ops.segment_sum(ones, ids, num_segments=S,
                                     use_pallas=True))
    kernel_s = time.perf_counter() - t0
    mx = np.asarray(ops.segment_max(vals, ids, num_segments=S,
                                    use_pallas=True))
    cnt_ref = np.asarray(jax.ops.segment_sum(ones, ids, num_segments=S))
    mx_ref = np.asarray(jax.ops.segment_max(vals, ids, num_segments=S))
    auto = ops.segment_sum.lower(ones, ids, num_segments=S).compile()
    counts = pl.counts_flat(flat)
    progs = [_kernel_program(ops.segment_sum, ones, ids, S),
             _kernel_program(ops.segment_max, vals, ids, S)]
    emit("segments", shape="counts_flat", elements=E, segments=S,
         sum_bitwise=bool(np.array_equal(cnt, cnt_ref)),
         max_bitwise=bool(np.array_equal(mx, mx_ref)),
         counts_flat_matches=bool(np.array_equal(
             counts.reshape(-1), cnt_ref)),
         auto_dispatch_kernel="tpu_custom_call" in auto.as_text(),
         tpu_custom_call=all("tpu_custom_call" in p for p in progs),
         kernel_sum_wall_s_informational=kernel_s)
    check(all("tpu_custom_call" in p for p in progs),
          "segment kernel ran without Mosaic")
    check(np.array_equal(cnt, cnt_ref), "counts_flat sums differ")
    check(np.array_equal(mx, mx_ref), "counts_flat maxima differ")
    check(np.array_equal(counts.reshape(-1), cnt_ref),
          "pipeline.counts_flat differs from jax.ops.segment_sum")
    check("tpu_custom_call" not in auto.as_text(),
          f"dispatch took the one-hot kernel for {S} segments")

    # aggregate_tier: 64 gateways of 64 linear-model devices each. A
    # group row sums 64 terms in the kernel's order and in jax.ops's;
    # each order is within 63 ulps of Σ|H·w|, the divide adds one more
    # per side: |kernel − ref| ≤ 128·eps·Σ|H·w| / H_g
    params, _ = eng.make_model("linear", jax.random.PRNGKey(0))
    W = {k: jnp.asarray(np.asarray(v)[None] + 0.1 * rng.standard_normal(
        (tier_m,) + v.shape).astype(np.float32)) for k, v in params.items()}
    H = jnp.asarray(rng.poisson(5.0, tier_m).astype(np.float32))
    gids = np.arange(tier_m) // (tier_m // groups)
    Wk, Hk = eng.aggregate_tier(W, H, gids, groups, use_pallas=True)
    Wr, Hr = eng.aggregate_tier(W, H, gids, groups, use_pallas=False)
    Wa, _ = eng.aggregate_tier({k: jnp.abs(v) for k, v in W.items()}, H,
                               gids, groups, use_pallas=False)
    worst = 0.0
    for k in W:
        err = np.abs(np.asarray(Wk[k]) - np.asarray(Wr[k]))
        bound = 128 * F32_EPS * np.asarray(Wa[k]) + 1e-30
        worst = max(worst, float((err / bound).max()))
    P = int(params["w"].size)
    leaf = jnp.asarray(np.asarray(W["w"]).reshape(-1))
    seg = jnp.asarray((gids[:, None] * P + np.arange(P)[None]).reshape(-1)
                      .astype(np.int32))
    tmx = np.asarray(ops.segment_max(leaf, seg, num_segments=groups * P,
                                     use_pallas=True))
    tmx_ref = np.asarray(jax.ops.segment_max(leaf, seg,
                                             num_segments=groups * P))
    prog = _kernel_program(ops.segment_sum, leaf, seg, groups * P)
    emit("segments", shape="aggregate_tier", stack=[tier_m, P],
         groups=groups, segments=groups * P,
         group_models_within_bound=worst <= 1.0,
         worst_err_over_bound=worst,
         group_weights_bitwise=bool(np.array_equal(np.asarray(Hk),
                                                   np.asarray(Hr))),
         max_bitwise=bool(np.array_equal(tmx, tmx_ref)),
         tpu_custom_call="tpu_custom_call" in prog)
    check("tpu_custom_call" in prog, "tier reduction ran without Mosaic")
    check(worst <= 1.0, f"group models off by {worst}x the bound")
    check(np.array_equal(np.asarray(Hk), np.asarray(Hr)),
          "group weight totals differ")
    check(np.array_equal(tmx, tmx_ref), "tier-leaf maxima differ")


def four_chip_phase(argv=PAPER_ARGV, chips=4):
    """The paper cell on the sharded engine over a 4-chip "data" mesh
    (n=10 padded to 12 with phantom devices) against the scan engine
    on one chip, same seeds."""
    import jax
    import numpy as np

    from repro.launch.mesh import make_data_mesh
    from repro.models import mnist as mm

    devs = jax.devices()[:chips]
    mesh = make_data_mesh(chips)
    sharded, h_sharded = run_cell("sharded", argv)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    # each chip holds a quarter of the (12, P) parameter stack at least
    n_params = sum(int(np.prod(s.shape)) for s in mm.cnn_specs().values())
    shard_bytes = 12 // chips * n_params * 4
    emit("four_chips", run="sharded", mesh={k: int(v) for k, v
                                            in mesh.shape.items()},
         mesh_devices=[str(d) for d in mesh.devices.reshape(-1)],
         peak_bytes_in_use=peaks, shard_bytes=shard_bytes, **sharded)
    check(all(p is not None and p >= shard_bytes for p in peaks),
          f"a chip never held its parameter shard: peaks {peaks}")
    scan, h_scan = run_cell("scan", argv)
    emit("four_chips", run="scan_one_chip", **scan)
    compare_runs("four_chips", sharded, h_sharded, scan, h_scan)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the sharded paper cell on a "
                         "4-chip mesh against scan on one chip")
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        sys.exit(f"chip_smoke: the repository's src/ is not next to this "
                 f"script ({e})")
    enable_compile_cache()
    devs = device_phase(args.chips)
    if args.chips == 4:
        four_chip_phase()
    else:
        paper_cell_phase()
        planner_phase()
        segments_phase()
    import jax

    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
