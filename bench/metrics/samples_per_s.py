"""Processed (unpadded) samples of all jobs in the window over the whole
window: from its start until the last job has returned its history to
the host, the generator's time between jobs included."""


def read(run):
    calls = run["calls"]
    if not calls:
        return None
    return sum(c["samples"] for c in calls) / run["window_s"]
