"""The benchmark's traffic generator: one general generator that every
traffic file (``bench/traffic/<name>.json``) parameterises.

It is the yardstick's own copy of the generators the program ships
(``data/synthetic.make_image_dataset``, ``core/costs.testbed_like_costs``,
``data/pipeline.poisson_streams``), so that a later change to the program
cannot move what the benchmark feeds it. Nothing here imports the
program; ``run.py`` wraps the arrays into the program's own types.

What a run sees:

* the dataset: what the configuration's model module
  (``bench/models/<model>.py``) makes from the configuration alone. The
  image models take ``image_dataset``: synthetic 28x28 images made from
  the configuration's ``data_seed`` (vectorised copy of the program's
  per-sample loop);
* the network: one cost trace drawn from the configuration's
  ``network_seed``. A deployment is one fog network, so every seed of a
  cell plans over the same devices and links, and the work of a call does
  not change with ``--seed``;
* one call's inputs, drawn from (``--seed``, call index): the network's
  costs with a fresh multiplicative jitter on every array (new
  measurements, never byte-identical to another call's), and fresh
  Poisson arrivals with fresh sample ids.
"""
from __future__ import annotations

import dataclasses

import numpy as np

N_CLASSES = 10
IMAGE = 28
MODES_PER_CLASS = 3      # prototypes a class mixes
NOISE = 0.65             # pixel noise
MAX_SHIFT = 3            # random shift of a prototype, in pixels
CHUNK = 8192             # images made at a time


def _smooth_noise(rng, shape, blur):
    x = rng.standard_normal(shape)
    for axis in (-2, -1):
        for _ in range(blur):
            x = 0.5 * x + 0.25 * (np.roll(x, 1, axis) + np.roll(x, -1, axis))
    return x


def image_dataset(n_train: int, n_test: int, seed: int):
    """(x_train, y_train, x_test, y_test): 10 classes of 28x28 float32
    images, each class a mixture of smooth prototypes with random shifts,
    amplitude jitter and pixel noise (the program's synthetic task)."""
    rng = np.random.default_rng(seed)
    protos = _smooth_noise(rng, (N_CLASSES, MODES_PER_CLASS, IMAGE, IMAGE),
                           blur=4)
    protos /= np.abs(protos).max(axis=(-2, -1), keepdims=True)
    protos = protos.astype(np.float32)
    ar = np.arange(IMAGE)

    def gen(n, rng):
        y = rng.integers(0, N_CLASSES, n)
        m = rng.integers(0, MODES_PER_CLASS, n)
        sx = rng.integers(-MAX_SHIFT, MAX_SHIFT + 1, n)
        sy = rng.integers(-MAX_SHIFT, MAX_SHIFT + 1, n)
        amp = rng.uniform(0.7, 1.3, n).astype(np.float32)
        x = np.empty((n, IMAGE, IMAGE), np.float32)
        for a in range(0, n, CHUNK):
            b = min(a + CHUNK, n)
            rows = (ar[None, :] - sx[a:b, None]) % IMAGE
            cols = (ar[None, :] - sy[a:b, None]) % IMAGE
            img = protos[y[a:b, None, None], m[a:b, None, None],
                         rows[:, :, None], cols[:, None, :]]
            x[a:b] = (amp[a:b, None, None] * img + NOISE * rng.standard_normal(
                (b - a, IMAGE, IMAGE), np.float32))
        return x, y.astype(np.int32)

    x_tr, y_tr = gen(n_train, rng)
    x_te, y_te = gen(n_test, np.random.default_rng(seed + 1))
    return x_tr, y_tr, x_te, y_te


def _ar1(rng, T, shape, phi=0.9, sigma=0.1):
    x = np.empty((T, *shape))
    x[0] = rng.random(shape)
    for t in range(1, T):
        x[t] = phi * x[t - 1] + (1 - phi) * rng.random(shape) \
            + sigma * rng.standard_normal(shape)
    return x


def _minmax(x):
    lo, hi = x.min(), x.max()
    return (x - lo) / (hi - lo + 1e-12)


@dataclasses.dataclass
class Costs:
    """Per-round costs of one plan horizon, float64: c_node (T, n),
    c_link (T, n, n), f_err (T, n)."""

    c_node: np.ndarray
    c_link: np.ndarray
    f_err: np.ndarray


def testbed_costs(n: int, T: int, rng, *, f_err: float, medium: str) -> Costs:
    """Correlated compute and link costs of the paper's Raspberry-Pi
    testbed: a latent device quality shared by a device's compute and
    link speeds, AR(1) noise in time, scaled to [0, 1]."""
    quality = rng.random(n)
    c_node = _minmax(0.7 * quality[None, :] + 0.3 * _ar1(rng, T, (n,)))
    link_base = 0.5 * (quality[None, :, None] + quality[None, None, :])
    scale, noise = {"wifi": (1.0, 0.25), "lte": (0.6, 0.12)}[medium]
    c_link = _minmax(link_base + noise * _ar1(rng, T, (n, n))) * scale
    return Costs(c_node=c_node, c_link=c_link, f_err=np.full((T, n), f_err))


@dataclasses.dataclass
class Call:
    """One call's inputs: costs, arrivals as per-(round, device) arrays of
    global sample ids, their counts D (T, n) float64, and the call's seed
    for the program's own random streams."""

    index: int
    costs: Costs
    cells: list
    D: np.ndarray
    seed: int


def _stream(seed: int, index: int) -> np.random.Generator:
    # --seed may exceed 32 bits; SeedSequence takes any non-negative int
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), int(index)])


class Traffic:
    """The generator of one cell: a configuration's network and dataset
    sizes, and a traffic file's parameters."""

    def __init__(self, config: dict, traffic: dict):
        self.n = int(config["n"])
        self.T = int(config["T"])
        self.n_train = int(config["n_train"])
        # arrivals per device-round: one epoch of the training set over
        # the job's horizon
        self.mean = self.n_train / (self.n * self.T)
        self.jitter = float(traffic["cost_jitter"])
        self.network = testbed_costs(
            self.n, self.T, np.random.default_rng(int(config["network_seed"])),
            f_err=float(config["f_err"]), medium=config["medium"])

    def call(self, seed: int, index: int) -> Call:
        rng = _stream(seed, index)
        net = self.network

        def jit(a):
            return a * (1.0 + self.jitter * (rng.random(a.shape) - 0.5))

        costs = Costs(c_node=jit(net.c_node), c_link=jit(net.c_link),
                      f_err=jit(net.f_err))
        k = rng.poisson(self.mean, (self.T, self.n)).reshape(-1)
        over = int(k.sum()) - self.n_train
        if over > 0:
            # an epoch: every training sample arrives at most once, so a
            # Poisson total above the set loses that many random arrivals
            slots = np.repeat(np.arange(k.size), k)
            drop = rng.choice(slots.size, over, replace=False)
            k = np.bincount(np.delete(slots, drop), minlength=k.size)
        k = k.reshape(self.T, self.n)
        total = int(k.sum())
        ids = rng.permutation(self.n_train)[:total].astype(np.int64)
        flat = np.split(ids, np.cumsum(k.reshape(-1))[:-1])
        cells = [flat[t * self.n:(t + 1) * self.n] for t in range(self.T)]
        return Call(index=index, costs=costs, cells=cells,
                    D=k.astype(np.float64),
                    seed=int(rng.integers(0, 2 ** 31 - 1)))
