"""A model whose size comes from its configuration, for the reference's
memory: rows of ``seq`` integer token ids, embedded (``vocab`` x
``width``) and averaged over the row, ``depth`` dense tanh layers of
``width`` x ``width``, and a linear head over ``classes``. The tests run it
at a few MB; at vocab 16,000, width 3,584 and depth 36 it holds 519.9M
float32 parameters (2.08 GB), more than the 2.03 GB that one of two chips
holds of Zamba2-7B's first seven layers."""
from __future__ import annotations

import numpy as np

import reference as ref

TEST_BLOCK = 8


def leaves(config):
    V, D = int(config["vocab"]), int(config["width"])
    C = int(config["classes"])
    out = {"emb": ((V, D), 1)}
    for i in range(int(config["depth"])):
        out[f"l{i}"] = ((D, D), D)
        out[f"b{i}"] = ((D,), None)
    out["head"] = ((D, C), D)
    out["hb"] = ((C,), None)
    return out


def dataset(config):
    rng = np.random.default_rng(int(config["data_seed"]))
    V, S, C = int(config["vocab"]), int(config["seq"]), int(config["classes"])

    def rows(n):
        x = rng.integers(0, V, (n, S)).astype(np.int32)
        return x, (x[:, 0] % C).astype(np.int32)

    return (*rows(int(config["n_train"])), *rows(int(config["n_test"])))


def program_model(config):
    return "wide"


def init(config, seed):
    return ref.gaussian_leaves(leaves(config), seed)


def apply(p, x, precision):
    import jax.numpy as jnp

    h = p["emb"][x].mean(axis=1)
    for i in range(sum(k.startswith("l") for k in p)):
        h = jnp.tanh(jnp.dot(h, p[f"l{i}"], precision=precision) + p[f"b{i}"])
    return jnp.dot(h, p["head"], precision=precision) + p["hb"]


def loss(config, p, x, y, w, precision):
    return ref.weighted_xent(apply(p, x, precision), y, w)


def test_loss(config, p, x_te, y_te, precision):
    return ref.blocked_xent(lambda x: apply(p, x, precision), x_te, y_te,
                            TEST_BLOCK)


def forward_flops(config):
    D, C = int(config["width"]), int(config["classes"])
    return (int(config["seq"]) * D + int(config["depth"]) * 2 * D * D
            + 2 * D * C)
