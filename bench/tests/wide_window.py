"""The reference's first window on a model too large to hold n times.

    python3 bench/tests/wide_window.py [--bench DIR]

Runs ``reference.first_window`` of the ``bench`` directory given (another
checkout's, to compare; this one's by default) on the wide fixture
(``fixtures/wide.py``) at the sizes below, over ``N`` devices in blocks of
``BLOCK``, and prints one JSON line: the parameters and bytes of one copy
of the weights, the seconds of each call, the device's peak bytes in use,
the bytes still in use once the calls have returned (the loaded
programs), and the temporaries of one step as the compiler counts them.
A call that runs out of device memory prints ``"ok": false`` with the
error and exits 1.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SEQ, CLASSES, ROWS, SEED, CALLS = 64, 5, 64, 2147483659, 2
# 519.9M float32 parameters (2.08 GB): a 2-chip share of a 7B hybrid model
VOCAB, WIDTH, DEPTH = 16000, 3584, 36
N, TAU, BLOCK = 10, 4, 1


def rounds(n, tau, rows, seed):
    """τ + 1 rounds of n devices' distinct sample ids, 1 to ``rows`` a
    cell."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, rows + 1, (tau + 1, n))
    ids = rng.permutation(int(sizes.sum()))
    flat = np.split(ids, np.cumsum(sizes.reshape(-1))[:-1])
    return [flat[t * n:(t + 1) * n] for t in range(tau + 1)]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--bench", default=os.path.dirname(HERE))
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.bench))
    import jax

    import reference as ref

    spec = importlib.util.spec_from_file_location(
        "bench_fixture_wide", os.path.join(HERE, "fixtures", "wide.py"))
    model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model)
    model.REF_DEVICE_BLOCK = BLOCK
    rs = rounds(N, TAU, ROWS, SEED)
    config = {"eta": 0.05, "max_points": 8, "data_seed": 4,
              "n_train": int(sum(len(ix) for row in rs for ix in row)),
              "n_test": 32 * model.TEST_BLOCK, "vocab": VOCAB,
              "seq": SEQ, "width": WIDTH, "depth": DEPTH,
              "classes": CLASSES}
    params = sum(int(np.prod(s)) for s, _ in model.leaves(config).values())
    data = model.dataset(config)
    dev = jax.devices()[0]
    out = {"reference": os.path.abspath(args.bench), "n": N,
           "tau": TAU, "block": BLOCK, "params": params,
           "weight_bytes": 4 * params, "device": dev.device_kind,
           "seconds": []}
    try:
        for _ in range(CALLS):
            t0 = time.perf_counter()
            losses, test_loss = ref.first_window(
                model, config, SEED, data, rs, precision="default")
            out["seconds"].append(time.perf_counter() - t0)
        stats = dev.memory_stats() or {}
        out["bytes_in_use_after"] = stats.get("bytes_in_use")
    except (jax.errors.JaxRuntimeError, ValueError) as e:
        # an eager operation reports an allocation it cannot make as a
        # ValueError
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        out.update(ok=False, error=str(e)[:600])
    else:
        out.update(ok=True, round0_loss=float(losses[0].mean()),
                   last_loss=float(losses[-1].mean()), test_loss=test_loss)
        # one block's step at its largest pad, as the compiler counts it
        step = ref._step_program(model, config, "default", False)
        w = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct((BLOCK,) + a.shape, a.dtype),
            jax.eval_shape(lambda: model.init(config, SEED)))
        P = max(8, 1 << (ROWS - 1).bit_length())
        x = jax.ShapeDtypeStruct((BLOCK, P, SEQ), np.int32)
        y = jax.ShapeDtypeStruct((BLOCK, P), np.int32)
        m = jax.ShapeDtypeStruct((BLOCK, P), np.float32)
        mem = step.lower(w, x, y, m).compile().memory_analysis()
        out["step_temp_bytes"] = getattr(mem, "temp_size_in_bytes", None)
    stats = dev.memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
