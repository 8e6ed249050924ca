"""Mean host time per job of the program's movement solve, its spans
``plan.greedy`` and ``plan.repair``, in ms."""
import os

import progtrace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(run):
    return progtrace.span_ms(run, ROOT, "plan.greedy", "plan.repair")
