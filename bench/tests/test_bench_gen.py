"""The traffic generator: deterministic per (seed, call), fresh across
calls, one epoch of distinct samples a job."""
from __future__ import annotations

import numpy as np

import gen

CONFIG = {"n": 16, "T": 6, "tau": 3, "n_train": 500, "network_seed": 4,
          "f_err": 0.7, "medium": "wifi"}
TRAFFIC = {"cost_jitter": 0.01}
BIG = 2 ** 33 + 12345          # more than 32 bits, as the driver's seeds


def _bytes(call):
    return [a.tobytes() for a in (call.costs.c_node, call.costs.c_link,
                                  call.costs.f_err)]


def test_same_seed_and_call_give_the_same_inputs():
    a = gen.Traffic(CONFIG, TRAFFIC).call(BIG, 3)
    b = gen.Traffic(CONFIG, TRAFFIC).call(BIG, 3)
    assert _bytes(a) == _bytes(b)
    assert np.array_equal(a.D, b.D) and a.seed == b.seed
    assert all(np.array_equal(x, y) for ra, rb in zip(a.cells, b.cells)
               for x, y in zip(ra, rb))


def test_no_two_calls_share_an_input_array():
    tg = gen.Traffic(CONFIG, TRAFFIC)
    calls = [tg.call(BIG, k) for k in range(4)] + [tg.call(BIG + 1, 0)]
    for k, a in enumerate(calls):
        for b in calls[k + 1:]:
            assert not set(_bytes(a)) & set(_bytes(b))
            assert a.D.tobytes() != b.D.tobytes() or not all(
                np.array_equal(x, y) for ra, rb in zip(a.cells, b.cells)
                for x, y in zip(ra, rb))


def test_a_job_is_one_epoch_of_distinct_samples():
    tg = gen.Traffic(dict(CONFIG, n_train=100), TRAFFIC)  # overshoots often
    for k in range(20):
        c = tg.call(7, k)
        ids = np.concatenate([ix for row in c.cells for ix in row])
        assert ids.size == c.D.sum() <= 100
        assert np.unique(ids).size == ids.size
        assert [[len(ix) for ix in row] for row in c.cells] == c.D.tolist()


def test_every_seed_plans_over_the_same_network():
    a = gen.Traffic(CONFIG, TRAFFIC).call(1, 0).costs.c_link
    b = gen.Traffic(CONFIG, TRAFFIC).call(2, 0).costs.c_link
    # jitter of at most 0.5% around one network
    assert np.allclose(a, b, rtol=0.011, atol=0)


def test_dataset_is_deterministic_and_shaped():
    a = gen.image_dataset(50, 20, 3)
    b = gen.image_dataset(50, 20, 3)
    assert a[0].shape == (50, 28, 28) and a[0].dtype == np.float32
    assert a[3].shape == (20,) and set(np.unique(a[1])) <= set(range(10))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
