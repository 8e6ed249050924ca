"""The program's ``linear`` model as a benchmark model module: 28x28
images of 10 classes, 4x4 average pooling down to 7x7, then one linear
layer. A test copies this file into ``bench/models/`` of a checkout to add
a model by files alone."""
from __future__ import annotations

import gen
import reference as ref

LEAVES = {"w": ((49, 10), 49), "b": ((10,), None)}


def dataset(config):
    return gen.image_dataset(int(config["n_train"]), int(config["n_test"]),
                             int(config["data_seed"]))


def program_model(config):
    return "linear"


def init(config, seed):
    return ref.gaussian_leaves(LEAVES, seed)


def apply(p, x, precision):
    import jax.numpy as jnp

    B = x.shape[0]
    h = x.reshape(B, 7, 4, 7, 4).mean(axis=(2, 4)).reshape(B, 49)
    return jnp.dot(h, p["w"], precision=precision) + p["b"]


def loss(config, p, x, y, w, precision):
    return ref.weighted_xent(apply(p, x, precision), y, w)


def test_loss(config, p, x_te, y_te, precision):
    return ref.blocked_xent(lambda x: apply(p, x, precision), x_te, y_te,
                            1000)


def forward_flops(config):
    """The pooling's adds and the linear layer's multiply-adds x 2."""
    return 28 * 28 + 2 * 49 * 10
