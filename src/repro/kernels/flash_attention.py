"""Pallas TPU flash attention (forward): blocked online-softmax with
causal and sliding-window support, GQA via K/V head index mapping.

Tiling: Q blocks (bq × hd) resident in VMEM; K/V streamed in (bk × hd)
blocks over the innermost (sequential, "arbitrary") grid dimension with
running (m, l, acc) scratch carried across K/V blocks. Fully-masked
blocks — above the causal diagonal or below the sliding-window band —
are skipped with ``pl.when``, which is the structural FLOP saving the
XLA lazy-blocked path cannot express (EXPERIMENTS.md §Perf).

Block sizes default to 128 (MXU-aligned); hd must be a multiple of 128
for peak MXU utilization but any value is functionally correct.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            scale: float, causal: bool, window: int | None,
            bq: int, bk: int, nk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q0 = qi * bq
    k0 = ki * bk

    # first/last K/V block this Q block actually visits
    if causal:
        last = jnp.minimum(nk - 1, (q0 + bq - 1) // bk)
    else:
        last = nk - 1
    if window is not None:
        first = jnp.maximum(q0 - (window - 1), 0) // bk
    else:
        first = 0

    @pl.when(ki == first)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    run = (ki >= first) & (ki <= last)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            ok &= k_pos <= q_pos
        if window is not None:
            ok &= k_pos > q_pos - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(ok, p, 0.0)
        l_sc[...] = l_sc[...] * alpha + p.sum(axis=1)
        acc_sc[...] = acc_sc[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(ki == last)
    def _finalize():
        denom = jnp.maximum(l_sc[...], 1e-30)[:, None]
        o_ref[0, 0, ...] = (acc_sc[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None, bq: int = 128, bk: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """q (B,H,Sq,hd); k,v (B,KH,Sk,hd) with H % KH == 0."""
    B, H, Sq, hd = q.shape
    KH, Sk = k.shape[1], k.shape[2]
    assert H % KH == 0, (H, KH)
    ratio = H // KH
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    kern = functools.partial(
        _kernel, scale=1.0 / np.sqrt(hd), causal=causal, window=window,
        bq=bq, bk=bk, nk=nk)
    grid = (B, H, nq, nk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki, ratio=ratio:
                         (b, h // ratio, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki, ratio=ratio:
                         (b, h // ratio, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
