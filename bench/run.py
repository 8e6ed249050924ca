"""Benchmark harness of the fog-learning system on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with a TPU. Everything a
cell is made of is found by name: the cell in ``BENCHMARK.json``, its
configuration in ``bench/configs/<config>.json``, the configuration's
model (its data, plain reference, FLOP count and the program's model
argument) in ``bench/models/<model>.py``, its traffic mix in
``bench/traffic/<traffic>.json``, the limits of its correctness check in
``bench/limits/<cell>.json`` and each metric's reader in
``bench/metrics/<metric>.py``. Adding a cell, a model, a traffic mix or
a metric is adding files and entries, never editing this harness.

A run: set-up (dataset, network, one warm-up job at the cell's shapes,
which compiles or loads from the compile cache everything the window
uses), then training jobs back to back until ``--seconds`` have passed
(the one in flight is finished), then the correctness check of a sample
of the window's jobs against the plain reference. Each job's inputs are
drawn from (seed, job index) before its timed span starts.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, ``breakdown`` with ``--trace 1``, and last ``checks``: each
number compared beside its limit. The same numbers are the last lines of
standard error. Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import gen  # noqa: E402


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell's entry of BENCHMARK.json with its configuration, traffic
    and limits files, all found by name."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    wl = cells[name]
    bench = os.path.join(root, "bench")
    config = load_json(os.path.join(bench, "configs", wl["config"] + ".json"))
    return {"spec": spec, "workload": wl, "config": config,
            "model": load_plugin("models", config["model"], root),
            "traffic": load_json(os.path.join(bench, "traffic",
                                              wl["traffic"] + ".json")),
            "limits": load_json(os.path.join(bench, "limits",
                                             name + ".json"))}


def cell_metrics(spec: dict, name: str, trace: bool) -> list:
    """The metrics a run of this cell reports: end-to-end ones with
    ``trace`` off, per-layer ones with it on."""
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def load_plugin(kind: str, name: str, root: str = ROOT):
    """The module ``bench/<kind>/<name>.py``, loaded by its path; an
    unknown name is an error that lists the known ones."""
    folder = os.path.join(root, "bench", kind)
    path = os.path.join(folder, name + ".py")
    if not os.path.isfile(path):
        known = sorted(f[:-3] for f in os.listdir(folder) if f.endswith(".py"))
        raise SystemExit(f"bench: no {kind} module {name!r} in {folder}; "
                         f"known: {known}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod



class Spans:
    """Host spans of the benchmark's own calls into each layer, kept in
    memory; with ``annotate`` also written into the profiler's trace."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.rows = []

    @contextlib.contextmanager
    def __call__(self, name: str, call: int):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation("bench:" + name)
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.rows.append((name, call, t0, time.perf_counter()))


def find_devices(chips: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (found {devs[0].platform}); "
                         "refusing to report")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, found "
                         f"{len(devs)}")
    return devs[:chips]


class System:
    """The system under test, driven through its own entry points: the
    planner (``movement.greedy_linear`` and, for capacity-limited
    settings, ``with_capacity`` and ``movement.repair_capacities``, as
    ``launch.train.solve_setting`` composes them), the host data plane
    (``federated._prepare_streams``) and the engine
    (``federated.run_network_aware`` on the scan engine). ``model`` is
    the configuration's model module, found by its name in this checkout
    where it is not given."""

    def __init__(self, config: dict, data, model=None):
        import numpy as np

        from repro.core import federated as F
        from repro.core import movement as mv
        from repro.core.costs import CostTraces, with_capacity
        from repro.data import pipeline as pl

        self.F, self.mv, self.pl = F, mv, pl
        self.CostTraces, self.with_capacity = CostTraces, with_capacity
        self.config, self.data = config, data
        model = model or load_plugin("models", config["model"])
        self.program_model = model.program_model(config)
        n = int(config["n"])
        if config["topology"] != "full":
            raise ValueError(f"topology {config['topology']!r}: only the "
                             "full topology is generated")
        self.adj = ~np.eye(n, dtype=bool)

    def inputs(self, call):
        """The program's types around one call's arrays (outside spans)."""
        import numpy as np

        c = call.costs
        T, n = c.c_node.shape
        traces = self.CostTraces(
            c_node=c.c_node, c_link=c.c_link, f_err=c.f_err,
            cap_node=np.full((T, n), np.inf),
            cap_link=np.full((T, n, n), np.inf))
        streams = self.pl.FogStreams(collected=call.cells, n=n, T=T)
        cfg = self.F.FedConfig(
            n=n, T=T, tau=int(self.config["tau"]),
            eta=float(self.config["eta"]), model=self.program_model,
            seed=call.seed, max_points=int(self.config["max_points"]))
        return traces, streams, cfg

    def run(self, call, span):
        """One job: plan, prep and engine; returns what the check reads."""
        import numpy as np

        traces, streams, cfg = self.inputs(call)
        out = {}
        k = call.index
        with span("call", k):
            with span("plan", k):
                with span("greedy", k):
                    tr = traces
                    if self.config["setting"] == "D":
                        tr = self.with_capacity(traces, float(call.D.mean()))
                    greedy = self.mv.greedy_linear(tr, self.adj)
                plan = greedy
                if self.config["setting"] == "D":
                    with span("repair", k):
                        plan = self.mv.repair_capacities(greedy, tr, self.adj,
                                                         call.D)
            with span("prep", k):
                prep = self.F._prepare_streams(cfg, self.data, plan,
                                               streams, None, None)
            with span("engine", k):
                hist = self.F.run_network_aware(
                    cfg, self.data, traces, None, plan, prepared=prep,
                    engine="scan")
        e = greedy.edges
        T, n = call.D.shape
        dec = np.full((T, n), -1, np.int64)
        dec[e.t, e.src] = e.dst
        out["greedy_dec"] = dec
        p = plan.edges
        out["plan"] = (p.t, p.src, p.dst, p.qty, plan.r)
        out["processed"] = prep[1]
        out["samples"] = int(sum(len(ix) for row in prep[1] for ix in row))
        out["hist"] = {key: hist[key] for key in
                       ("device_loss", "test_loss", "H_agg", "agg_round",
                        "processed_counts")}
        out["aggregations"] = len(hist["agg_round"])
        return out


def main(argv=None, *, require_tpu: bool = True, root: str = ROOT) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, root)
    wl, config, traffic = cell["workload"], cell["config"], cell["traffic"]
    model = cell["model"]
    metrics = cell_metrics(cell["spec"], args.workload, bool(args.trace))
    readers = {m["name"]: load_plugin("metrics", m["name"], root).read
               for m in metrics}

    if os.path.join(root, "src") not in sys.path:
        sys.path.insert(0, os.path.join(root, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    import numpy as np

    # every program goes to the persistent cache, so that only a cell's
    # first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = find_devices(int(wl["chips"]), require_tpu)
    from repro.core import monitoring

    import check

    data = model.dataset(config)
    traffic_gen = gen.Traffic(config, traffic)
    sut = System(config, data, model)
    spans = Spans(annotate=bool(args.trace))
    outs = {}
    # warm-up: one call at the cell's own shapes
    outs[0] = sut.run(traffic_gen.call(args.seed, 0), spans)
    trace_dir = os.path.join(root, "bench", "out", "trace")
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    compiles0 = monitoring.compile_events()
    setup_s = time.perf_counter() - T_START
    k = 0
    with spans("window", -1):
        t_win = time.perf_counter()
        while time.perf_counter() - t_win < args.seconds:
            k += 1
            with spans("gen", k):
                call = traffic_gen.call(args.seed, k)
            outs[k] = sut.run(call, spans)
        window_s = time.perf_counter() - t_win
    compiles = monitoring.compile_events() - compiles0
    if args.trace:
        jax.profiler.stop_trace()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devs)
    calls = []
    for idx in range(1, k + 1):
        st = {}
        for name, c, t0, t1 in spans.rows:
            if c == idx:
                st[name] = st.get(name, 0.0) + (t1 - t0)
        calls.append({"index": idx, "span_s": st["call"], "stages": st,
                      "samples": outs[idx].get("samples"),
                      "aggregations": outs[idx].get("aggregations")})
    spans_s = sorted(c["span_s"] for c in calls)
    print(f"bench: {k} calls in the window, {compiles} compiles in the "
          f"window, setup {setup_s:.3f} s, window {window_s:.3f} s, call "
          f"spans min {spans_s[0]:.4f} median {spans_s[len(spans_s) // 2]:.4f}"
          f" max {spans_s[-1]:.4f} s", file=sys.stderr, flush=True)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    red = None
    if args.trace:
        import xplane

        red = xplane.reduce_dir(trace_dir)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    import flops

    device["peaks"] = flops.peaks(device["kind"], os.path.join(
        root, "bench", "peaks.json"))
    run = {"workload": args.workload, "config": config, "model": model,
           "traffic": traffic,
           "setup_s": setup_s, "window_s": window_s, "calls": calls,
           "device": device, "chips": len(devs), "trace": red,
           "compiles_in_window": compiles}
    values = {}
    for m in metrics:
        v = readers[m["name"]](run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # correctness: a sample of the window's jobs, drawn from the seed,
    # against the reference on inputs drawn again from (seed, index)
    rng = np.random.default_rng([args.seed & (2 ** 64 - 1), 7])
    pool = list(range(1, k + 1))
    pick = sorted(rng.choice(pool, min(int(traffic["check_calls"]),
                                       len(pool)), replace=False))
    per_call = []
    for idx in pick:
        call = traffic_gen.call(args.seed, int(idx))
        per_call.append(check.call_numbers(call, outs[int(idx)], config,
                                           model, sut.adj, data))
    correct, failed, rows = check.judge(per_call, cell["limits"])
    device.pop("peaks")
    result = {"correct": correct, "attempted": k, "failed": failed,
              "metrics": values, "device": device}
    if red is not None:
        import xplane

        result["breakdown"] = xplane.breakdown(red)
    result["checks"] = rows
    for name, row in rows.items():
        print(f"check {name} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
