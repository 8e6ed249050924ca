"""Pallas TPU kernel for the Theorem-3 offload decision rule.

For large fog networks (n up to 10⁴+ shards in the production mapping)
the per-round decision is an O(n²) masked min-plus reduction:
    k_i = argmin_{j : (i,j)∈E} ( c_ij + c_j(t+1) ),
followed by the 3-way marginal-cost comparison {process, offload,
discard}. The (n × n) effective-cost matrix is streamed through VMEM in
(bn × bn) tiles; a running (min, argmin) per row is carried across the
column-tile grid dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INF = 3.4e38  # python float: jnp scalars would be captured as consts


def _kernel(clink_ref, cnext_ref, cnode_ref, ferr_ref, adj_ref,
            choice_ref, bestj_ref, bestc_ref, min_sc, arg_sc, *,
            bn: int, ncols: int):
    ri = pl.program_id(0)
    cj = pl.program_id(1)

    @pl.when(cj == 0)
    def _init():
        min_sc[...] = jnp.full_like(min_sc, INF)
        arg_sc[...] = jnp.zeros_like(arg_sc)

    eff = (clink_ref[...].astype(jnp.float32)
           + cnext_ref[0][None, :].astype(jnp.float32))      # (bn, bn)
    row = ri * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
    col = cj * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1)
    ok = adj_ref[...] & (row != col)
    eff = jnp.where(ok, eff, INF)

    tile_min = eff.min(axis=1)
    tile_arg = (cj * bn + jnp.argmin(eff, axis=1)).astype(jnp.int32)
    better = tile_min < min_sc[...]
    arg_sc[...] = jnp.where(better, tile_arg, arg_sc[...])
    min_sc[...] = jnp.where(better, tile_min, min_sc[...])

    @pl.when(cj == ncols - 1)
    def _finalize():
        proc = cnode_ref[0].astype(jnp.float32)
        disc = ferr_ref[0].astype(jnp.float32)
        off = min_sc[...]
        # 3-way argmin with ties resolved process < offload < discard
        best = jnp.minimum(jnp.minimum(proc, off), disc)
        choice = jnp.where(proc <= best, 0,
                           jnp.where(off <= best, 1, 2)).astype(jnp.int32)
        choice_ref[0, ...] = choice
        bestj_ref[0, ...] = arg_sc[...]
        bestc_ref[0, ...] = best


def offload_greedy(c_link, c_next, c_node, f_err, adj, *, bn: int = 128,
                   interpret: bool | None = None):
    """Theorem 3 rule. c_link (n,n); c_next,c_node,f_err (n,); adj (n,n)
    bool. Returns (choice (n,) int32, best_j (n,) int32, best_cost (n,)).

    Matches ``ref.offload_greedy_ref`` (up to argmin tie order).
    """
    n = c_node.shape[0]
    bn = min(bn, n)
    assert n % bn == 0, (n, bn)
    nb = n // bn
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    kern = functools.partial(_kernel, bn=bn, ncols=nb)
    choice, bestj, bestc = pl.pallas_call(
        kern,
        grid=(nb, nb),
        in_specs=[
            pl.BlockSpec((bn, bn), lambda ri, cj: (ri, cj)),  # c_link
            pl.BlockSpec((1, bn), lambda ri, cj: (0, cj)),    # c_next
            pl.BlockSpec((1, bn), lambda ri, cj: (0, ri)),    # c_node
            pl.BlockSpec((1, bn), lambda ri, cj: (0, ri)),    # f_err
            pl.BlockSpec((bn, bn), lambda ri, cj: (ri, cj)),  # adj
        ],
        out_specs=[
            pl.BlockSpec((1, bn), lambda ri, cj: (0, ri)),
            pl.BlockSpec((1, bn), lambda ri, cj: (0, ri)),
            pl.BlockSpec((1, bn), lambda ri, cj: (0, ri)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bn,), jnp.float32),
                        pltpu.VMEM((bn,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(c_link, c_next[None, :], c_node[None, :], f_err[None, :], adj)
    return choice[0], bestj[0], bestc[0]


def offload_greedy_batched(c_link, c_next, c_node, f_err, adj, *,
                           bn: int = 128, interpret: bool | None = None):
    """All-rounds Theorem 3 rule: leading time axis T on every operand.

    c_link (T,n,n); c_next, c_node, f_err (T,n); adj (T,n,n) bool.
    vmap lifts the round axis onto the Pallas grid, so the whole horizon
    is one kernel launch. Returns (choice (T,n), best_j (T,n),
    best_cost (T,n)).
    """
    kern = functools.partial(offload_greedy, bn=bn, interpret=interpret)
    return jax.vmap(kern)(c_link, c_next, c_node, f_err, adj)


def offload_greedy_edges(c_link, c_next, c_node, f_err, adj, *,
                         bn: int = 128, interpret: bool | None = None):
    """Batched Theorem-3 rule with device-side COO edge emission.

    Runs the min-plus kernel for all T rounds, then materializes the
    sparse movement plane directly: fixed-shape ``(T·n,)`` edge arrays
    ``(t, src, dst)`` plus a keep-mask (False on discard decisions,
    whose rows become ``r`` instead of an edge). The (T, n, n) dense
    share tensor is never built — the host packs the masked arrays
    straight into a ``PlanEdges`` COO list.

    Returns (t_idx, src, dst, keep, choice), all (T·n,) except
    ``choice`` which stays (T, n) for diagnostics.
    """
    choice, best_j, _ = offload_greedy_batched(
        c_link, c_next, c_node, f_err, adj, bn=bn, interpret=interpret)
    T, n = choice.shape
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (T, n), 0).reshape(-1)
    src = jax.lax.broadcasted_iota(jnp.int32, (T, n), 1).reshape(-1)
    flat = choice.reshape(-1)
    dst = jnp.where(flat == 1, best_j.reshape(-1), src)
    keep = flat != 2
    return t_idx, src, dst, keep, choice
