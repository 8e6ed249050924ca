"""The paper's CNN (arXiv:2004.08488 §V-A) on 28x28 images of 10 classes:
conv 5x5 (16), pool, conv 5x5 (32), pool, 1568-128-10, as the program's
``models/mnist`` defines it."""
from __future__ import annotations

import gen
import reference as ref

# leaf -> (shape, fan-in) of a gaussian leaf, or (shape, None) for zeros
LEAVES = {"c1": ((5, 5, 1, 16), 25), "cb1": ((16,), None),
          "c2": ((5, 5, 16, 32), 400), "cb2": ((32,), None),
          "w1": ((1568, 128), 1568), "b1": ((128,), None),
          "w2": ((128, 10), 128), "b2": ((10,), None)}


def dataset(config):
    return gen.image_dataset(int(config["n_train"]), int(config["n_test"]),
                             int(config["data_seed"]))


def program_model(config):
    return "cnn"


def init(config, seed):
    return ref.gaussian_leaves(LEAVES, seed)


def apply(p, x, precision):
    import jax
    import jax.numpy as jnp

    def conv(h, w, b):
        y = jax.lax.conv_general_dilated(
            h, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=precision)
        return jnp.maximum(y + b, 0)

    def pool(h):
        B, H, W, C = h.shape
        return h.reshape(B, H // 2, 2, W // 2, 2, C).max(axis=(2, 4))

    h = pool(conv(x[..., None], p["c1"], p["cb1"]))
    h = pool(conv(h, p["c2"], p["cb2"]))
    h = h.reshape(h.shape[0], -1)
    h = jnp.maximum(jnp.dot(h, p["w1"], precision=precision) + p["b1"], 0)
    return jnp.dot(h, p["w2"], precision=precision) + p["b2"]


def loss(config, p, x, y, w, precision):
    return ref.weighted_xent(apply(p, x, precision), y, w)


def test_loss(config, p, x_te, y_te, precision):
    # blocks of 1,000 test images keep the activations small
    return ref.blocked_xent(lambda x: apply(p, x, precision), x_te, y_te,
                            1000)


def _conv_flops(h, w, cin, cout, k):
    return 2 * h * w * cout * k * k * cin


def forward_flops(config):
    """Multiply-adds x 2 of one image's forward pass."""
    return (_conv_flops(28, 28, 1, 16, 5) + _conv_flops(14, 14, 16, 32, 5)
            + 2 * 1568 * 128 + 2 * 128 * 10)
