"""Mean device time per job of the ops under the scope ``aggregate``: eq.
(4) aggregation, synchronization and guarded uploads, in ms (device
trace)."""
import os

import progtrace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(run):
    return progtrace.scope_ms(run, ROOT, "aggregate")
