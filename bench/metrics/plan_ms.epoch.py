"""Mean host time per job of the benchmark's span around the plan stage,
in ms."""


def read(run):
    calls = run["calls"]
    if not calls:
        return None
    return 1e3 * sum(c["stages"]["plan"] for c in calls) / len(calls)
