"""Mean host time per job of the program's span ``prep.route``: the
data plane routing each sample per the plan, in ms."""
import os

import progtrace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(run):
    return progtrace.span_ms(run, ROOT, "prep.route")
