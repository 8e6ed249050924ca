"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp
oracle, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.offload_greedy import offload_greedy
from repro.kernels.ssd_scan import ssd_scan

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


@pytest.mark.parametrize("B,H,KH,S,hd", [
    (1, 2, 2, 128, 64),
    (2, 4, 2, 256, 64),     # GQA 2:1
    (1, 8, 1, 256, 32),     # MQA
    (2, 2, 2, 384, 16),     # 3 blocks, odd head_dim
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, H, KH, S, hd, causal, dtype):
    q, k, v = (_rand((B, H, S, hd), dtype),
               _rand((B, KH, S, hd), dtype),
               _rand((B, KH, S, hd), dtype))
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [32, 128, 200])
def test_flash_attention_sliding_window(window):
    B, H, S, hd = 1, 2, 256, 64
    q, k, v = (_rand((B, H, S, hd), jnp.float32),
               _rand((B, H, S, hd), jnp.float32),
               _rand((B, H, S, hd), jnp.float32))
    out = flash_attention(q, k, v, causal=True, window=window,
                          interpret=True, bq=64, bk=64)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_block_shapes():
    B, H, S, hd = 1, 1, 512, 64
    q, k, v = (_rand((B, H, S, hd), jnp.float32),) * 3
    ref_out = ref.flash_attention_ref(q, k, v, causal=True)
    for bq, bk in [(64, 128), (128, 64), (256, 256)]:
        out = flash_attention(q, k, v, causal=True, bq=bq, bk=bk,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,H,S,P,N,chunk", [
    (1, 2, 128, 32, 16, 32),
    (2, 4, 256, 64, 64, 128),
    (1, 1, 64, 16, 128, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_matches_ref(B, H, S, P, N, chunk, dtype):
    xdt = jnp.asarray(RNG.standard_normal((B, H, S, P)) * 0.3, dtype)
    a = jnp.asarray(-np.abs(RNG.standard_normal((B, H, S))) * 0.3,
                    jnp.float32)
    Bm = jnp.asarray(RNG.standard_normal((B, S, N)) * 0.3, dtype)
    Cm = jnp.asarray(RNG.standard_normal((B, S, N)) * 0.3, dtype)
    out = ssd_scan(xdt, a, Bm, Cm, chunk=chunk, interpret=True)
    want = ref.ssd_scan_ref(xdt, a, Bm, Cm)
    tol = 1e-4 if dtype == jnp.float32 else 4e-2
    scale = float(jnp.abs(want).max()) + 1e-9
    np.testing.assert_allclose(np.asarray(out) / scale,
                               np.asarray(want) / scale, atol=tol)


def test_ssd_scan_state_carry_across_many_chunks():
    """Long-range dependency: early impulse must influence late outputs."""
    B, H, S, P, N = 1, 1, 256, 8, 8
    xdt = jnp.zeros((B, H, S, P)).at[0, 0, 3].set(1.0)
    a = jnp.full((B, H, S), -0.01)
    Bm = jnp.ones((B, S, N)) * 0.5
    Cm = jnp.ones((B, S, N)) * 0.5
    out = ssd_scan(xdt, a, Bm, Cm, chunk=64, interpret=True)
    want = ref.ssd_scan_ref(xdt, a, Bm, Cm)
    assert float(jnp.abs(out[0, 0, -1]).max()) > 1e-3  # signal survived
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("n,bn,density", [
    (128, 128, 0.3), (256, 128, 0.1), (512, 128, 0.9), (128, 64, 0.5),
])
def test_offload_greedy_matches_ref(n, bn, density):
    c_link = jnp.asarray(RNG.random((n, n)), jnp.float32)
    c_next = jnp.asarray(RNG.random(n), jnp.float32)
    c_node = jnp.asarray(RNG.random(n), jnp.float32)
    f_err = jnp.asarray(RNG.random(n), jnp.float32)
    adj = jnp.asarray(RNG.random((n, n)) < density)
    got = offload_greedy(c_link, c_next, c_node, f_err, adj, bn=bn,
                         interpret=True)
    want = ref.offload_greedy_ref(c_link, c_next, c_node, f_err, adj)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                               rtol=1e-6)


def test_offload_greedy_isolated_nodes_never_offload():
    n = 128
    adj = jnp.zeros((n, n), bool)
    choice, _, _ = offload_greedy(
        jnp.zeros((n, n)), jnp.zeros(n),
        jnp.asarray(RNG.random(n), jnp.float32),
        jnp.asarray(RNG.random(n), jnp.float32), adj, interpret=True)
    assert not bool(jnp.any(choice == 1))


@pytest.mark.parametrize("E,S", [(1, 1), (1000, 300), (2048, 128)])
@pytest.mark.parametrize("op", ["sum", "max"])
def test_segment_reduce_matches_jax_ops(E, S, op):
    """Unsorted ids, empty segments and element padding; integer-valued
    data makes every summation order exact, so both ops match bitwise."""
    from repro.kernels import segment_reduce as sr

    rng = np.random.default_rng(E + S)
    ids = jnp.asarray(rng.integers(0, S, E), jnp.int32)
    vals = jnp.asarray(rng.integers(-8, 9, E), jnp.float32)
    kernel = sr.segment_sum_pallas if op == "sum" else sr.segment_max_pallas
    want = (jax.ops.segment_sum if op == "sum" else jax.ops.segment_max)(
        vals, ids, num_segments=S)
    got = kernel(vals, ids, S, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("E,S,kernel", [
    (256, 1024, True),            # at both bounds
    (255, 64, False),             # too few elements
    (4096, 1025, False),          # one segment past the bound
    (512_000, 512_000, False),    # counts_flat at n=10,240, T=50
])
def test_segment_dispatch_bounds_the_kernel_grid(monkeypatch, E, S, kernel):
    """On an accelerator the one-hot kernel is taken only where its
    E·⌈S/128⌉ work stays within 8 passes over the elements."""
    from functools import partial

    from repro.kernels import ops

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    data = jax.ShapeDtypeStruct((E,), jnp.float32)
    ids = jax.ShapeDtypeStruct((E,), jnp.int32)
    for fn in (ops.segment_sum, ops.segment_max):
        jaxpr = jax.make_jaxpr(partial(fn.__wrapped__, num_segments=S))(
            data, ids)
        assert ("pallas_call" in str(jaxpr)) == kernel
