"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The benchmark writes its own host spans into the trace with
``jax.profiler.TraceAnnotation`` under names ``bench:<span>``; the span
``bench:window`` covers the measured window. Device planes are those
named ``/device:TPU:<k>``; their operations are the events of the line
``XLA Ops``. All times are taken on the trace's clock, in seconds.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

PREFIX = "bench:"
OPS_LINE = "XLA Ops"
_OPCODE = re.compile(r" ([a-z][a-z0-9_.-]*)\(")


def short_name(hlo: str) -> str:
    """``%fusion.186 fusion`` of an op named by its whole HLO text
    (``%fusion.186 = bf16[...] fusion(...), kind=...``): its result name
    and its opcode."""
    if " = " not in hlo:
        return hlo
    lhs, rhs = hlo.split(" = ", 1)
    m = _OPCODE.search(rhs)
    return f"{lhs} {m.group(1)}" if m else lhs


def _union(intervals):
    """Total length and merged list of [start, end) intervals."""
    if not intervals:
        return 0.0, []
    iv = sorted(intervals)
    merged = [list(iv[0])]
    for a, b in iv[1:]:
        if a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


def reduce_events(host, devices, window=None):
    """The reduction on plain event lists.

    ``host``: [(name, start_s, end_s)] of the benchmark's spans (without
    the prefix). ``devices``: one list per chip of (op name, start_s,
    end_s). ``window``: (start_s, end_s), default the ``window`` span.

    Returns busy_s (union of op intervals within the window, averaged
    over chips), window_s, ops ({name: device seconds summed over chips}),
    op_counts, and idle ({host span name: idle seconds}, each gap of
    chip 0 named by the innermost host span other than the window that
    covers its midpoint, or "other").
    """
    if window is None:
        ws = [(a, b) for name, a, b in host if name == "window"]
        if not ws:
            raise ValueError("trace holds no window span")
        window = ws[0]
    w0, w1 = window
    busy, ops, counts, merged0 = [], {}, {}, None
    for k, evs in enumerate(devices):
        clipped = [(max(a, w0), min(b, w1)) for _, a, b in evs
                   if b > w0 and a < w1]
        total, merged = _union(clipped)
        busy.append(total)
        if k == 0:
            merged0 = merged
        for name, a, b in evs:
            if b > w0 and a < w1:
                ops[name] = ops.get(name, 0.0) + (min(b, w1) - max(a, w0))
                counts[name] = counts.get(name, 0) + 1
    return {"busy_s": float(np.mean(busy)) if busy else 0.0,
            "window_s": w1 - w0, "ops": ops, "op_counts": counts,
            "idle": _idle_by_span(merged0 or [], host, w0, w1)}


def _idle_by_span(merged, host, w0, w1):
    """Idle seconds of one chip within [w0, w1), each idle instant given
    to the innermost host span (other than the window) that covers it."""
    spans = sorted(((a, b, name) for name, a, b in host if name != "window"),
                   key=lambda s: s[1] - s[0])      # innermost first
    cuts = [w0, w1] + [p for a, b in merged for p in (a, b)]
    cuts += [p for a, b, _ in spans for p in (a, b)]
    pts = np.unique(np.clip(np.asarray(cuts, np.float64), w0, w1))
    if pts.size < 2:
        return {}
    lo, hi = pts[:-1], pts[1:]
    mid = 0.5 * (lo + hi)
    if merged:
        starts = np.asarray([a for a, _ in merged])
        ends = np.asarray([b for _, b in merged])
        k = np.searchsorted(starts, mid, side="right") - 1
        busy = (k >= 0) & (mid < ends[np.maximum(k, 0)])
    else:
        busy = np.zeros(mid.shape, bool)
    mid, width = mid[~busy], (hi - lo)[~busy]
    who = np.full(mid.shape, len(spans))
    for s, (a, b, _) in reversed(list(enumerate(spans))):
        who[(a <= mid) & (mid < b)] = s
    names = [name for _, _, name in spans] + ["other"]
    per = np.bincount(who, weights=width, minlength=len(names))
    idle = {}
    for name, w in zip(names, per):
        if w > 0:
            idle[name] = idle.get(name, 0.0) + float(w)
    return idle


def load(trace_dir: str):
    """Host spans and per-chip device ops of the newest trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((short_name(e.name), e.start_ns * 1e-9,
                                e.end_ns * 1e-9) for e in line.events)
            devices.append(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        host.append((e.name[len(PREFIX):], e.start_ns * 1e-9,
                                     e.end_ns * 1e-9))
    return host, devices


def reduce_dir(trace_dir: str):
    host, devices = load(trace_dir)
    return reduce_events(host, devices)


def breakdown(red, top: int = 10):
    """The device operations that took most time, and the idle seconds
    by what the host was doing, each as [[name, seconds], ...]."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(red["idle"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}


def describe(trace_dir: str, top: int = 15) -> dict:
    """Planes, their lines and the busiest event names of the newest
    trace: what to look at before matching names in a reader."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    pd = ProfileData.from_file(files[-1])
    out = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            tot = {}
            for e in line.events:
                tot[e.name] = tot.get(e.name, 0.0) + e.duration_ns * 1e-9
            ev = list(line.events)
            lines[line.name] = {
                "span_s": [min((e.start_ns for e in ev), default=0) * 1e-9,
                           max((e.end_ns for e in ev), default=0) * 1e-9],
                "events": len(ev),
                "top": sorted(tot.items(), key=lambda kv: -kv[1])[:top]}
        out[plane.name] = lines
    return out


if __name__ == "__main__":
    import json
    import sys

    print(json.dumps(describe(sys.argv[1]), indent=1))
