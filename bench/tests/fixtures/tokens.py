"""A model of integer token rows for the generic reference's tests: each
row is ``seq`` token ids, embedded, averaged over the row and classified
by a linear head."""
from __future__ import annotations

import numpy as np

import reference as ref


def dataset(config):
    rng = np.random.default_rng(int(config["data_seed"]))
    V, S, C = int(config["vocab"]), int(config["seq"]), int(config["classes"])

    def rows(n):
        x = rng.integers(0, V, (n, S)).astype(np.int32)
        return x, (x[:, 0] % C).astype(np.int32)

    return (*rows(int(config["n_train"])), *rows(int(config["n_test"])))


def program_model(config):
    return "tokens"


def init(config, seed):
    V, D = int(config["vocab"]), int(config["width"])
    C = int(config["classes"])
    return ref.gaussian_leaves({"emb": ((V, D), 1), "w": ((D, C), D),
                                "b": ((C,), None)}, seed)


def apply(p, x, precision):
    import jax.numpy as jnp

    h = p["emb"][x].mean(axis=1)
    return jnp.dot(h, p["w"], precision=precision) + p["b"]


def loss(config, p, x, y, w, precision):
    return ref.weighted_xent(apply(p, x, precision), y, w)


def test_loss(config, p, x_te, y_te, precision):
    return ref.blocked_xent(lambda x: apply(p, x, precision), x_te, y_te, 20)


def forward_flops(config):
    D, C = int(config["width"]), int(config["classes"])
    return int(config["seq"]) * D + 2 * D * C
