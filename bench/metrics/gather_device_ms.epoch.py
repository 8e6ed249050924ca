"""Mean device time per job of the ops under the scope ``gather``: the
pixel gathers (prestaged, and inside the scan), in ms (device trace)."""
import os

import progtrace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(run):
    return progtrace.scope_ms(run, ROOT, "gather")
