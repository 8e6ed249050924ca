"""Mean bytes per job the engine uploads to the device as it stages a
horizon (counter ``h2d_bytes`` of ``train.stage``), in MB (1e6 B)."""
import os

import progtrace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(run):
    b = progtrace.counter(run, ROOT, "train.stage", "h2d_bytes")
    return None if b is None else b / 1e6 / len(run["calls"])
