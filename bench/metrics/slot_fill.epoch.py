"""Share of the engine's executed (T, n, P) sample slots that hold a
sample: the counters ``samples`` over ``slots`` of the window's
``train.stage`` spans, in %."""
import os

import progtrace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(run):
    samples = progtrace.counter(run, ROOT, "train.stage", "samples")
    slots = progtrace.counter(run, ROOT, "train.stage", "slots")
    if samples is None or not slots:
        return None
    return 100.0 * samples / slots
