"""The paper's MLP 784-200-10 (arXiv:2004.08488 §V-A) on 28x28 images of
10 classes, as the program's ``models/mnist`` defines it."""
from __future__ import annotations

import gen
import reference as ref

# leaf -> (shape, fan-in) of a gaussian leaf, or (shape, None) for zeros
LEAVES = {"w1": ((784, 200), 784), "b1": ((200,), None),
          "w2": ((200, 10), 200), "b2": ((10,), None)}


def dataset(config):
    return gen.image_dataset(int(config["n_train"]), int(config["n_test"]),
                             int(config["data_seed"]))


def program_model(config):
    return "mlp"


def init(config, seed):
    return ref.gaussian_leaves(LEAVES, seed)


def apply(p, x, precision):
    import jax.numpy as jnp

    h = jnp.dot(x.reshape(x.shape[0], -1), p["w1"],
                precision=precision) + p["b1"]
    h = jnp.maximum(h, 0)
    return jnp.dot(h, p["w2"], precision=precision) + p["b2"]


def loss(config, p, x, y, w, precision):
    return ref.weighted_xent(apply(p, x, precision), y, w)


def test_loss(config, p, x_te, y_te, precision):
    # blocks of 1,000 test images keep the activations small
    return ref.blocked_xent(lambda x: apply(p, x, precision), x_te, y_te,
                            1000)


def forward_flops(config):
    """Multiply-adds x 2 of one image's forward pass."""
    return 2 * 784 * 200 + 2 * 200 * 10
