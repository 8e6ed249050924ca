"""Model modules found by name: the paper's CNN and MLP read what the
harness read before it took models as modules, and the generic reference
steps token rows and blocks of devices.

``fixtures/parent_readings.json`` holds the readings of the reference as
it was before the models moved into ``bench/models/``: the first window's
losses and test loss of both models with and without each planted fault,
a dataset's SHA-256 and the FLOP counts. XLA's CPU backend splits a
reduction by the threads it has, so its last bit depends on the cores a
process sees; the readings are taken in a child process held to one core,
where they are a property of the code alone.
"""
from __future__ import annotations

import functools
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
FAULTS = [None, "unchanged", "half_batch", "no_broadcast"]
CONFIG = {"eta": 0.1, "max_points": 16, "n_train": 300, "n_test": 1000,
          "data_seed": 3}


def _rounds(n=3, tau=2, n_train=300, seed=5):
    """τ + 1 rounds of n devices' sample ids, distinct, some cells empty."""
    rng = np.random.default_rng(seed)
    ids = rng.permutation(n_train)
    out, at = [], 0
    for _ in range(tau + 1):
        row = []
        for i in range(n):
            k = int(rng.integers(0, 20)) if i else int(rng.integers(5, 20))
            row.append(ids[at:at + k].astype(np.int64))
            at += k
        out.append(row)
    return out


def _readings():
    import flops
    import gen
    import reference as ref
    from run import load_plugin

    res = {"first_window": {}, "forward_flops": {}, "job_flops": {}}
    for name in ("cnn", "mlp"):
        model = load_plugin("models", name)
        data = model.dataset(CONFIG)
        for fault in FAULTS:
            losses, tl = ref.first_window(model, CONFIG, 11, data, _rounds(),
                                          precision="default", fault=fault)
            res["first_window"][f"{name}-{fault}"] = {
                "losses": [[float(v).hex() for v in r] for r in losses],
                "test_loss": float(tl).hex()}
        res["forward_flops"][name] = model.forward_flops(CONFIG)
        res["job_flops"][name] = flops.job_flops({"n_test": 10_000}, model,
                                                 57_700, 10)
    d = gen.image_dataset(50, 20, 3)
    res["dataset_sha256"] = hashlib.sha256(
        b"".join(np.ascontiguousarray(a).tobytes() for a in d)).hexdigest()
    return res


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.abspath(__file__)], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.splitlines()[-1])


@pytest.fixture(scope="module")
def parent():
    with open(os.path.join(HERE, "fixtures", "parent_readings.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("model", ["cnn", "mlp"])
def test_first_window_reads_as_before_bit_for_bit(readings, parent, model,
                                                   fault):
    key = f"{model}-{fault}"
    assert readings["first_window"][key] == parent["first_window"][key]


@pytest.mark.parametrize("model", ["cnn", "mlp"])
def test_flops_read_as_before(readings, parent, model):
    assert readings["forward_flops"][model] == parent["forward_flops"][model]
    assert readings["job_flops"][model] == parent["job_flops"][model]


def test_image_dataset_reads_as_before(readings, parent):
    assert readings["dataset_sha256"] == parent["dataset_sha256"]


# --------------------------------------------------------------------------
# the generic reference on a model of integer token rows
# --------------------------------------------------------------------------

TOKENS = {"eta": 0.5, "max_points": 8, "n_train": 300, "n_test": 40,
          "data_seed": 9, "vocab": 50, "seq": 12, "width": 16, "classes": 5}
LINEAR = {"eta": 0.1, "max_points": 16, "n_train": 300, "n_test": 1000,
          "data_seed": 3}
# a few MB of weights: its leaves' shapes are held by nothing else
WIDE = {"eta": 0.05, "max_points": 8, "n_train": 400, "n_test": 40,
        "data_seed": 4, "vocab": 1000, "seq": 6, "width": 256, "depth": 4,
        "classes": 5}
CONFIGS = {"tokens": TOKENS, "linear": LINEAR, "wide": WIDE}


def _fixture(name, **over):
    """The fixture ``fixtures/<name>.py`` as a fresh module object, with
    ``over`` set on it."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"bench_fixture_{name}", os.path.join(HERE, "fixtures", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for k, v in over.items():
        setattr(mod, k, v)
    return mod


def _token_rounds(n=4, tau=2, empty=False):
    """τ + 1 rounds of n devices' distinct sample ids, 1 to 8 a cell (0 to
    8 with ``empty``)."""
    rng = np.random.default_rng(1)
    ids = rng.permutation(300)
    sizes = rng.integers(0 if empty else 1, 9, (tau + 1, n))
    assert sizes.sum() <= ids.size
    cuts = np.cumsum(sizes.reshape(-1))[:-1]
    flat = np.split(ids[:sizes.sum()], cuts)
    return [flat[t * n:(t + 1) * n] for t in range(tau + 1)]


def test_token_rows_reach_the_loss_as_integers():
    import reference as ref

    seen = []
    plain = _fixture("tokens").loss

    def loss(config, p, x, y, w, precision):
        seen.append(x.dtype)
        return plain(config, p, x, y, w, precision)

    model = _fixture("tokens", loss=loss)
    data = model.dataset(TOKENS)
    assert data[0].dtype == np.int32
    ref.first_window(model, TOKENS, 3, data, _token_rounds(),
                     precision="highest")
    assert seen and all(d == np.int32 for d in seen)


N_DEVICES = 10


@functools.lru_cache(maxsize=None)
def _one_block(name, fault, dtype):
    """The first window of ten devices stepped all at once."""
    import reference as ref

    model = _fixture(name)
    return ref.first_window(model, CONFIGS[name], 3,
                            model.dataset(CONFIGS[name]),
                            _token_rounds(N_DEVICES, empty=True),
                            precision="highest", dtype=dtype, fault=fault)


# A block's step is another program than the ten devices' step, on batches
# of another pad, and eq. (4) adds the blocks in another order. In float32
# that moves a loss by a float32 step or two (2.1e-7 at most on an x86
# CPU); in bfloat16 it read no gap there, but a sum that rounds the other
# way moves a bfloat16 log-probability, and so a loss, by one bfloat16 step
# (2^-8 relative).
RTOL = {"float32": 1e-6, "bfloat16": 2.0 ** -8}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("block", [1, 3, N_DEVICES])
@pytest.mark.parametrize("name", ["tokens", "linear"])
def test_devices_stepped_in_blocks_read_as_all_at_once(name, block, fault,
                                                       dtype):
    """Blocks of 1 and 3 devices (3 does not divide 10) walk the window
    device-major and read as the ten devices stepped at once; a block of
    all ten is the same program."""
    import reference as ref

    whole = _one_block(name, fault, dtype)
    model = _fixture(name, REF_DEVICE_BLOCK=block)
    blocks = ref.first_window(model, CONFIGS[name], 3,
                              model.dataset(CONFIGS[name]),
                              _token_rounds(N_DEVICES, empty=True),
                              precision="highest", dtype=dtype, fault=fault)
    # the losses move over the window, so the blocks did train
    assert np.ptp(whole[0]) > 0
    if block == N_DEVICES:
        np.testing.assert_array_equal(blocks[0], whole[0])
        assert blocks[1] == whole[1]
        return
    np.testing.assert_allclose(blocks[0], whole[0], rtol=RTOL[dtype])
    assert blocks[1] == pytest.approx(whole[1], rel=RTOL[dtype])


@pytest.mark.parametrize("block", [1, 3])
def test_blocked_reference_never_holds_n_copies_of_the_weights(monkeypatch,
                                                                block):
    """Ten devices in blocks of b: sampled around every step and the test
    loss, no live array holds ten copies of a weight leaf, and the live
    weights (the initial ones, the eq. (4) accumulator, a block's weights
    before and after a step) never pass (2 + 2b) |θ|."""
    import jax

    import reference as ref

    model = _fixture("wide", REF_DEVICE_BLOCK=block)
    leaves = [s for s, _ in model.leaves(WIDE).values()]
    shapes = set(leaves)
    theta = sum(4 * int(np.prod(s)) for s in leaves)
    samples = []

    def sample():
        stacked, held = [], 0
        for a in jax.live_arrays():
            if a.shape[:1] == (N_DEVICES,) and a.shape[1:] in shapes:
                stacked.append(a.shape)
            if a.shape in shapes or (a.ndim and a.shape[0] <= block
                                     and a.shape[1:] in shapes):
                held += a.nbytes
        samples.append((stacked, held))

    def watched(make):
        def program(*args):
            run = make(*args)

            def call(*xs):
                sample()
                out = run(*xs)
                sample()
                return out
            return call
        return program

    monkeypatch.setattr(ref, "_step_program", watched(ref._step_program))
    monkeypatch.setattr(ref, "_test_program", watched(ref._test_program))
    for fault in (None, "no_broadcast"):
        ref.first_window(model, WIDE, 5, model.dataset(WIDE),
                         _token_rounds(N_DEVICES, tau=3),
                         precision="highest", fault=fault)
    assert samples
    assert [s for s, _ in samples if s] == []
    assert max(h for _, h in samples) <= (2 + 2 * block) * theta


if __name__ == "__main__":
    os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
    sys.path.insert(0, BENCH)
    print(json.dumps(_readings()))
