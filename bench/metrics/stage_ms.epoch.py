"""Mean host time per job of the engine's span ``train.stage``: padding
the (T, n, P) slots, their uploads and the prestaged gather's dispatch,
in ms."""
import os

import progtrace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(run):
    return progtrace.span_ms(run, ROOT, "train.stage")
