"""Mean device time per job of the ops under the scope ``local_sgd``: the
vmapped local SGD step of eq. (3), in ms (device trace)."""
import os

import progtrace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(run):
    return progtrace.scope_ms(run, ROOT, "local_sgd")
