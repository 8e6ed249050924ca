"""The program's part of a profiler trace: its own spans and counters,
device time by scope, and the chip's idle time by what the program was
doing.

The program writes each step of its host work
(``repro.core.monitoring.span``) into the trace as ``fog:<name>``, its
counters as stats of the event; the benchmark's spans are
``bench:<name>``, and ``bench:window`` covers the measured window. Device
ops are the events of the line ``XLA Ops`` on ``/device:TPU:<k>``, each
inside an event of the line ``XLA Modules`` named ``<module>(<id>)``. No
stat of an op event carries its ``jax.named_scope`` path on the TPU, so
an op's scope is read from the ``op_name`` metadata of the instruction
the event is named by, in the optimized HLO of the executables the
process holds. Scopes: ``gather``, ``local_sgd``, ``aggregate`` and
``eval``. Container ops (``while``, ``conditional``, ``call``) are left
out: the ops of their bodies are counted.

    python3 bench/progtrace.py bench/out/trace

prints the self time of each span, the idle attribution and the device
time by scope, with the top ops of the unscoped remainder, reading the
scopes that the traced run saved beside the trace (``scopes.json``).
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
import time

import xplane

FOG = "fog:"
SCOPES = ("gather", "local_sgd", "aggregate", "eval")
CONTAINERS = frozenset({"while", "conditional", "call"})
MODULES_LINE = "XLA Modules"
SCOPE_FILE = "scopes.json"
_INSTR = re.compile(r"\s*(?:ROOT\s+)?%([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def scope_of(op_name: str):
    """The scope an ``op_name`` path lies in, or None. Only the path's
    components before the last count: the last names the primitive,
    which may itself be called ``gather``."""
    for part in reversed(op_name.split("/")[:-1]):
        words = _WORD.findall(part)
        for s in SCOPES:
            if s in words:
                return s
    return None


def hlo_scopes(hlo_text: str) -> dict:
    """{instruction: scope} of one optimized HLO module's text, for the
    instructions whose ``op_name`` lies in a scope."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        op = m and _OP_NAME.search(line)
        s = op and scope_of(op.group(1))
        if s:
            out[m.group(1)] = s
    return out


def live_scope_maps(modules) -> dict:
    """{module name: [{instruction: scope} of each executable of that
    name]} over the executables this process holds."""
    from jax.extend.backend import get_backend

    out = {}
    for ex in get_backend().live_executables():
        for mod in ex.hlo_modules():
            if mod.name in modules:
                out.setdefault(mod.name, []).append(
                    hlo_scopes(mod.to_string()))
    return out


def module_base(module: str) -> str:
    """``jit_fog_scan`` of a module event named ``jit_fog_scan(1234)``."""
    return module.split("(", 1)[0]


def instruction(hlo: str) -> str:
    """``fusion.15`` of an op event named by its HLO text
    (``%fusion.15 = bf16[...] fusion(...)``) or by its name alone."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _self_times(spans):
    """Per name: calls, seconds, self seconds (less the spans opened
    inside it) and the sum of each counter, of [(name, start, end,
    stats)] spans of one thread."""
    out, stack = {}, []
    for name, a, b, stats in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][2] <= a:
            stack.pop()
        row = out.setdefault(name, {"calls": 0, "seconds": 0.0,
                                    "self_seconds": 0.0})
        row["calls"] += 1
        row["seconds"] += b - a
        row["self_seconds"] += b - a
        if stack:
            out[stack[-1][0]]["self_seconds"] -= b - a
        for k, v in stats.items():
            if not k.startswith("_") and isinstance(v, (int, float)):
                row[k] = row.get(k, 0) + v
        stack.append((name, a, b))
    return out


def _pick_map(base, instrs, scope_maps):
    """The scope map of the executable named ``base`` that knows most of
    the instructions seen in its module events."""
    cands = scope_maps.get(base)
    if not cands:
        return None
    return max(cands, key=lambda m: sum(i in m for i in instrs))


def reduce_events(fog, bench, chips, scope_maps, window=None):
    """The reduction on plain event lists.

    ``fog``: [(name, start_s, end_s, stats)] of the program's spans.
    ``bench``: [(name, start_s, end_s)] of the benchmark's. ``chips``:
    one iterable per chip of (module, op name, start_s, end_s).
    ``scope_maps``: {module name: [{instruction: scope}]}, or None where
    no map is known. ``window``: (start_s, end_s), default the
    ``window`` span.

    Returns window_s; calls (``call`` spans in the window); spans (per
    name, of the fog spans inside the window: calls, seconds,
    self_seconds and each counter's sum); scopes ({scope: device
    seconds summed over chips}, None without maps); unscoped_ops ({op:
    device seconds} of ops in no scope); idle (chip 0's idle seconds in
    the window by the innermost ``fog:`` span, else the innermost
    ``bench:`` span, else ``other``).
    """
    if window is None:
        ws = [(a, b) for name, a, b in bench if name == "window"]
        if not ws:
            raise ValueError("trace holds no window span")
        window = ws[0]
    w0, w1 = window
    spans = [s for s in fog if s[1] >= w0 and s[2] <= w1]
    per_op, busy0 = {}, []
    for k, evs in enumerate(chips):
        for module, name, a, b in evs:
            if b <= w0 or a >= w1:
                continue
            a, b = max(a, w0), min(b, w1)
            if k == 0:
                busy0.append((a, b))
            key = (module, name)
            per_op[key] = per_op.get(key, 0.0) + (b - a)
    scopes, unscoped = None, {}
    if scope_maps is not None:
        scopes, seen = {}, {}
        for module, name in per_op:
            seen.setdefault(module, set()).add(instruction(name))
        maps = {m: _pick_map(module_base(m), instrs, scope_maps)
                for m, instrs in seen.items()}
        for (module, name), sec in per_op.items():
            short = xplane.short_name(name)
            if short.rsplit(" ", 1)[-1] in CONTAINERS:
                continue
            s = (maps[module] or {}).get(instruction(name))
            if s is None:
                unscoped[short] = unscoped.get(short, 0.0) + sec
            else:
                scopes[s] = scopes.get(s, 0.0) + sec
    _, merged = xplane._union(busy0)
    host = [(name, a, b) for name, a, b, _ in spans]
    idle = {FOG + k: v for k, v in
            xplane._idle_by_span(merged, host, w0, w1).items()
            if k != "other"}
    # where no fog span covers an idle instant: the benchmark's spans
    _, covered = xplane._union(merged + [[a, b] for _, a, b in host])
    for k, v in xplane._idle_by_span(covered, bench, w0, w1).items():
        idle[k if k == "other" else xplane.PREFIX + k] = v
    return {"window_s": w1 - w0,
            "calls": sum(1 for name, a, b in bench
                         if name == "call" and a >= w0 and b <= w1),
            "spans": _self_times(spans), "scopes": scopes,
            "unscoped_ops": unscoped, "idle": idle}


def newest(trace_dir: str):
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


def _chip_ops(plane):
    """(module, op name, start_s, end_s) of one device plane's ops, each
    given the module event it runs in."""
    lines = {line.name: line for line in plane.lines}
    mods = sorted((e.start_ns, e.end_ns, e.name)
                  for e in (lines[MODULES_LINE].events
                            if MODULES_LINE in lines else ()))
    starts = [m[0] for m in mods]
    if xplane.OPS_LINE not in lines:
        return
    for e in lines[xplane.OPS_LINE].events:
        a = e.start_ns
        k = bisect.bisect_right(starts, a) - 1
        module = mods[k][2] if k >= 0 and a < mods[k][1] else ""
        yield module, e.name, a * 1e-9, e.end_ns * 1e-9


def load(path: str):
    """fog spans, bench spans and the per-chip op iterables of one
    ``.xplane.pb``, and the module names its device planes ran."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    fog, bench, devices = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name.startswith(FOG):
                        fog.append((name[len(FOG):], e.start_ns * 1e-9,
                                    e.end_ns * 1e-9, dict(e.stats)))
                    elif name.startswith(xplane.PREFIX):
                        bench.append((name[len(xplane.PREFIX):],
                                      e.start_ns * 1e-9, e.end_ns * 1e-9))
    modules = set()
    for plane in devices:
        for line in plane.lines:
            if line.name == MODULES_LINE:
                modules.update(module_base(e.name) for e in line.events)
    return fog, bench, [_chip_ops(p) for p in devices], modules


def reduce_file(path: str, scope_maps):
    """The reduction of one trace file with the given scope maps."""
    fog, bench, chips, _ = load(path)
    return reduce_events(fog, bench, chips, scope_maps)


def saved_scope_maps(path: str):
    """The scope maps a traced run saved beside the trace, or None."""
    side = os.path.join(os.path.dirname(path), SCOPE_FILE)
    if not os.path.exists(side):
        return None
    with open(side) as f:
        return json.load(f)


_CACHE: dict = {}


def for_root(root: str):
    """The reduction of the newest trace under ``<root>/bench/out/trace``,
    made once per trace file; None where there is none."""
    path = newest(os.path.join(root, "bench", "out", "trace"))
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        t0 = time.perf_counter()
        fog, bench, chips, modules = load(path)
        maps = live_scope_maps(modules)
        with open(os.path.join(os.path.dirname(path), SCOPE_FILE),
                  "w") as f:
            json.dump(maps, f)
        red = reduce_events(fog, bench, chips, maps)
        red["reduce_s"] = time.perf_counter() - t0
        print(f"progtrace: {path} reduced in {red['reduce_s']:.2f} s",
              file=sys.stderr, flush=True)
        _CACHE[key] = red
    return _CACHE[key]


# ---------------------------------------------------------------------------
# what the metrics read: means per job of the window, None where absent
# ---------------------------------------------------------------------------


def _red(run, root):
    if run.get("trace") is None or not run["calls"]:
        return None
    return for_root(root)


def span_seconds(run, root, *names):
    """Summed seconds of the spans ``names`` in the window, None where
    none of them ran."""
    red = _red(run, root)
    rows = [red["spans"][n] for n in names
            if red is not None and n in red["spans"]]
    return sum(r["seconds"] for r in rows) if rows else None


def span_ms(run, root, *names):
    """Mean host ms a job spent in the spans ``names``."""
    s = span_seconds(run, root, *names)
    return None if s is None else 1e3 * s / len(run["calls"])


def counter(run, root, span: str, key: str):
    """The sum of counter ``key`` over the window's ``span`` spans."""
    red = _red(run, root)
    if red is None or key not in red["spans"].get(span, {}):
        return None
    return red["spans"][span][key]


def scope_ms(run, root, scope: str):
    """Mean device ms a job spent in ops of ``scope``."""
    red = _red(run, root)
    sec = red and (red["scopes"] or {}).get(scope)
    return 1e3 * sec / len(run["calls"]) if sec else None


def _print(red, top: int = 12):
    print(f"window {red['window_s']:.3f} s, {red['calls']} calls")
    print("span                  calls    seconds  self_seconds  counters")
    for name, row in sorted(red["spans"].items()):
        extra = {k: v for k, v in row.items()
                 if k not in ("calls", "seconds", "self_seconds")}
        print(f"{name:20s} {row['calls']:6d} {row['seconds']:10.4f} "
              f"{row['self_seconds']:13.4f}  {extra or ''}")
    print("idle by span (chip 0)")
    for name, v in sorted(red["idle"].items(), key=lambda kv: -kv[1]):
        print(f"  {name:28s} {v:10.4f}")
    if red["scopes"] is None:
        print("device time by scope: no scope map")
        return
    print("device time by scope (all chips)")
    for name, v in sorted(red["scopes"].items(), key=lambda kv: -kv[1]):
        print(f"  {name:28s} {v:10.4f}")
    rest = sorted(red["unscoped_ops"].items(), key=lambda kv: -kv[1])
    print(f"  {'unscoped':28s} {sum(v for _, v in rest):10.4f}")
    for name, v in rest[:top]:
        print(f"    {name[:60]:60s} {v:10.4f}")


if __name__ == "__main__":
    path = newest(sys.argv[1])
    if path is None:
        raise SystemExit(f"no .xplane.pb under {sys.argv[1]}")
    _print(reduce_file(path, saved_scope_maps(path)))
