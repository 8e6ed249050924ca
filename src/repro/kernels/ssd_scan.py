"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid (B, H, n_chunks) with the chunk dimension innermost & sequential
("arbitrary"): the (P × N) inter-chunk state lives in VMEM scratch and is
carried across chunk iterations — the TPU-native replacement for the
GPU kernel's warp-level chunk pipeline. Per chunk, the intra-chunk
quadratic piece is three MXU matmuls: scores = C·Bᵀ (l×l), masked-decay
weighting, and (l×l)·(l×P); the state update is one (P×l)·(l×N) matmul.

Chunk length defaults to 128 (MXU-aligned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, a_ref, b_ref, c_ref, y_ref, state_sc, *, l: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_sc[...] = jnp.zeros_like(state_sc)

    x = x_ref[0, 0].astype(jnp.float32)       # (l, P)
    a = a_ref[0, 0].astype(jnp.float32)       # (l,)
    Bm = b_ref[0].astype(jnp.float32)         # (l, N)
    Cm = c_ref[0].astype(jnp.float32)         # (l, N)

    cs = jnp.cumsum(a)                        # (l,)
    # intra-chunk: L[i,j] = exp(cs_i - cs_j) for i >= j
    diff = cs[:, None] - cs[None, :]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (l, l), 1))
    Lmat = jnp.where(tri, jnp.exp(diff), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y_diag = jax.lax.dot_general(scores * Lmat, x,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    # contribution of the carried state
    state = state_sc[...]                     # (P, N)
    y_off = jnp.exp(cs)[:, None] * jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)   # (l, P)

    y_ref[0, 0, ...] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: S <- exp(cs_last)·S + Σ_j exp(cs_last - cs_j) x_j ⊗ B_j
    decay = jnp.exp(cs[-1] - cs)              # (l,)
    xw = x * decay[:, None]                   # (l, P)
    contrib = jax.lax.dot_general(xw, Bm, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (P,N)
    state_sc[...] = jnp.exp(cs[-1]) * state + contrib


def ssd_scan(xdt, a, Bm, Cm, *, chunk: int = 128,
             interpret: bool | None = None) -> jax.Array:
    """xdt (B,H,S,P); a (B,H,S); Bm,Cm (B,S,N). Returns y (B,H,S,P) f32.

    Matches ``ref.ssd_scan_ref`` (sequential recurrence oracle).
    """
    B, H, S, P = xdt.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    kern = functools.partial(_kernel, l=chunk)
    return pl.pallas_call(
        kern,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), jnp.float32),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xdt, a, Bm, Cm)
