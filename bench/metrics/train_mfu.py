"""Required training FLOPs of the jobs in the window (forward and
backward on every processed sample, the test-set forward at every
aggregation) over the window times the chips' bf16 peak, in %."""
import flops


def read(run):
    calls = run["calls"]
    if not calls:
        return None
    cfg = run["config"]
    work = sum(flops.job_flops(cfg, run["model"], c["samples"],
                               c["aggregations"]) for c in calls)
    peak = run["device"]["peaks"]["flops_bf16"]
    return 100.0 * work / (run["window_s"] * run["chips"] * peak)
