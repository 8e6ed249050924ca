"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / window, in %."""


def read(run):
    red = run["trace"]
    if red is None:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
