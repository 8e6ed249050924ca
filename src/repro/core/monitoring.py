"""The program's one telemetry module: host spans with counters, and the
single process-wide XLA compile-event registration.

Spans. ``span(name, **counters)`` marks one step of the program's host
work::

    from repro.core import monitoring
    with monitoring.span("train.stage", slots=T * n * P) as sp:
        ...
        sp.count(samples=int(counts.sum()))
    monitoring.totals()   # {"train.stage": {"calls", "seconds",
                          #   "self_seconds", "slots", "samples"}}
    monitoring.reset()

Each span always does two things. It opens a
``jax.profiler.TraceAnnotation`` named ``fog:<name>`` with its counters
as metadata, so that whenever a profiler session runs the span lands in
the trace's host plane on the same clock as the device ops (about 1 µs
when none runs). And it adds to in-memory per-name totals: calls,
seconds, self seconds (seconds less the time of the spans opened inside
it, tracked on a context-local stack) and the sum of each counter.
``count`` adds counters known only once the work is done. A counter is
an ``int`` the code already holds or reads off shapes; no counter costs a
pass over the data, a device sync or a readback.

Compile events. ``jax.monitoring`` listeners cannot be unregistered, so
every module that wants compile telemetry must NOT call
``register_event_duration_secs_listener`` itself: before this module
existed the cost-model EMA (``costmodel.install_listener``) and the
benchmark compile counter (``benchmarks.run``) each registered their
own global hook, which meant import order decided how many listeners
ran per compile and a future third consumer would have made the
duplication worse. Now there is exactly one registration, installed
lazily on first use, that fans events out to subscribers:

    monitoring.subscribe_compile(lambda seconds: ...)
    monitoring.compile_events()     # process-wide compile count

``compile_events`` counts ``backend_compile`` events since installation
(0 forever if ``jax.monitoring`` is unavailable) — the recompile
watchdog in :mod:`repro.core.sanitize` and the benchmark provenance
stamps both take deltas of it, so they share one counter instead of
three drifting ones.
"""
from __future__ import annotations

import contextvars
import time
from typing import Callable

from jax.profiler import TraceAnnotation

SPAN_PREFIX = "fog:"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_SUBSCRIBERS: list = []
_STATE = {"installed": False, "failed": False, "events": 0}


def _ensure_installed() -> None:
    if _STATE["installed"] or _STATE["failed"]:
        return
    import jax

    def _on_event(name, *a, **kw):
        if name != COMPILE_EVENT:
            return
        dur = a[0] if a else kw.get("duration_secs", 0.0)
        try:
            dur = float(dur)
        except (TypeError, ValueError):
            dur = 0.0
        _STATE["events"] += 1
        for fn in tuple(_SUBSCRIBERS):
            try:
                fn(dur)
            except Exception:
                # a broken subscriber must never take down the compile
                # path (the listener runs inside jit dispatch) or
                # starve the other subscribers
                pass

    try:
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _STATE["installed"] = True
    except Exception:
        _STATE["failed"] = True


def subscribe_compile(fn: Callable[[float], None]) -> Callable[[float], None]:
    """Add ``fn(duration_secs)`` to the fan-out (idempotent per fn)."""
    _ensure_installed()
    if fn not in _SUBSCRIBERS:
        _SUBSCRIBERS.append(fn)
    return fn


def unsubscribe_compile(fn: Callable[[float], None]) -> None:
    try:
        _SUBSCRIBERS.remove(fn)
    except ValueError:
        pass


def compile_events() -> int:
    """backend_compile events observed since the listener installed."""
    _ensure_installed()
    return _STATE["events"]


def listener_installed() -> bool:
    _ensure_installed()
    return _STATE["installed"]


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

_TOTALS: dict = {}
_OPEN: contextvars.ContextVar = contextvars.ContextVar("fog_open_span",
                                                       default=None)


class span:
    """``with span(name, **counters) as sp:`` — one step of host work,
    written to the profiler's trace as ``fog:<name>`` and added to the
    totals; ``sp.count(**counters)`` adds counters known only at the
    end."""

    __slots__ = ("name", "_ann", "_counts", "_t0", "_child", "_token")

    def __init__(self, name: str, **counters: int):
        self.name = name
        self._counts = counters
        self._ann = TraceAnnotation(SPAN_PREFIX + name, **counters)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._child = 0.0
        self._token = _OPEN.set(self)
        self._t0 = time.perf_counter()
        return self

    def count(self, **counters: int) -> None:
        self._counts.update(counters)
        self._ann.set_metadata(**counters)

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        _OPEN.reset(self._token)
        parent = _OPEN.get()
        if parent is not None:
            parent._child += dt
        row = _TOTALS.get(self.name)
        if row is None:
            row = _TOTALS[self.name] = {"calls": 0, "seconds": 0.0,
                                        "self_seconds": 0.0}
        row["calls"] += 1
        row["seconds"] += dt
        row["self_seconds"] += dt - self._child
        for key, v in self._counts.items():
            row[key] = row.get(key, 0) + v
        self._ann.__exit__(*exc)


def totals() -> dict:
    """Per span name since the last :func:`reset`: ``calls``,
    ``seconds``, ``self_seconds`` and the sum of each counter."""
    return {name: dict(row) for name, row in _TOTALS.items()}


def reset() -> None:
    _TOTALS.clear()
