"""Mean host time per job of the engine outside its compiled program:
the span ``train`` less ``train.device`` (dispatch through
``block_until_ready``), in ms."""
import os

import progtrace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(run):
    train = progtrace.span_seconds(run, ROOT, "train")
    device = progtrace.span_seconds(run, ROOT, "train.device")
    if train is None or device is None:
        return None
    return 1e3 * (train - device) / len(run["calls"])
