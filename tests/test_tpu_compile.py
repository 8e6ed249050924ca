"""The main path's Pallas kernels compiled for a described TPU v5e.

The TPU compiler is installed with jaxlib and compiles for a chip that
is described, not attached, so Mosaic's refusals (block shapes off the
(8, 128) tiling, too much VMEM) surface here at no chip time. Each test
compiles one kernel at the size the main path stages and checks that
the program holds the Mosaic custom call — that is, the kernel was not
lowered in interpret mode. Nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

# counts_flat at the sparse_scale CI point: n=10,240 devices, T=50
# rounds, one sample per device-round on average
FLAT_ELEMS, FLAT_SEGMENTS = 512_000, 512_000
# aggregate_tier over a 4096-device stack of the linear model, 64 groups
TIER_M, TIER_GROUPS = 4096, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("op", ["sum", "max"])
def test_segment_reduce_compiles_at_counts_flat_shape(one_chip, op):
    from repro.kernels import segment_reduce as sr

    fn = sr.segment_sum_pallas if op == "sum" else sr.segment_max_pallas
    compiled = jax.jit(
        lambda d, i: fn(d, i, FLAT_SEGMENTS, interpret=False)).lower(
        _sds((FLAT_ELEMS,), jnp.float32, one_chip),
        _sds((FLAT_ELEMS,), jnp.int32, one_chip)).compile()
    _assert_mosaic(compiled)


def test_offload_greedy_edges_compiles(one_chip):
    from repro.kernels.offload_greedy import offload_greedy_edges

    n, T = 1024, 50
    f32 = _sds((T, n), jnp.float32, one_chip)
    compiled = jax.jit(
        lambda *a: offload_greedy_edges(*a, interpret=False)).lower(
        _sds((T, n, n), jnp.float32, one_chip), f32, f32, f32,
        _sds((T, n, n), jnp.bool_, one_chip)).compile()
    _assert_mosaic(compiled)


def test_aggregate_tier_leaf_reduction_compiles(one_chip, monkeypatch):
    from repro.core import engine as eng
    from repro.models import mnist as mm

    # the kernel picks interpret mode from the default backend, which
    # is the CPU here; the described chip is the target
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    gids = np.arange(TIER_M) // (TIER_M // TIER_GROUPS)
    W = {k: _sds((TIER_M,) + s.shape, jnp.float32, one_chip)
         for k, s in mm.linear_specs().items()}
    H = _sds((TIER_M,), jnp.float32, one_chip)
    compiled = jax.jit(lambda W, H: eng.aggregate_tier(
        W, H, gids, TIER_GROUPS, use_pallas=True)).lower(W, H).compile()
    _assert_mosaic(compiled)
