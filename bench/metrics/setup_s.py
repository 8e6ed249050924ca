"""Process start until the first timed span: JAX start-up, dataset and
network generation, the warm-up call and any compilation."""


def read(run):
    return run["setup_s"]
