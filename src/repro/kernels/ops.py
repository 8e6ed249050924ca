"""Jit'd public wrappers over the Pallas kernels.

On CPU (this container) kernels run in interpret mode — the kernel body
executes in Python for correctness validation; on TPU they compile to
Mosaic. ``use_pallas=False`` falls back to the pure-jnp reference (the
oracle), which is also what the model code uses by default on CPU.
"""
from __future__ import annotations

from functools import partial

import jax

import jax.numpy as jnp

import numpy as np

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.offload_greedy import (offload_greedy,
                                          offload_greedy_batched,
                                          offload_greedy_edges)
from repro.kernels.segment_reduce import (segment_max_pallas,
                                          segment_sum_pallas)
from repro.kernels.ssd_scan import ssd_scan

# dispatch segment reductions to the Pallas kernel above this element
# count (accelerators only — on CPU the kernel runs in interpret mode
# and the fused jnp scatter wins); mirrors movement.PALLAS_MIN_N
PALLAS_MIN_N = 256
# ...and only up to this many segments: the one-hot kernel visits every
# element once per 128-segment tile, so its work is E·⌈S/128⌉ and an
# unbounded S (counts_flat has S = T·n) would make its grid quadratic
PALLAS_MAX_SEGMENTS = 1024


def _segment_kernel_default(n_elems: int, num_segments: int) -> bool:
    return (jax.default_backend() != "cpu" and n_elems >= PALLAS_MIN_N
            and num_segments <= PALLAS_MAX_SEGMENTS)


@partial(jax.jit, static_argnames=("causal", "window", "use_pallas"))
def attention(q, k, v, *, causal=True, window=None, use_pallas=True):
    if use_pallas:
        return flash_attention(q, k, v, causal=causal, window=window)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window)


@partial(jax.jit, static_argnames=("chunk", "use_pallas"))
def ssd(xdt, a, Bm, Cm, *, chunk=128, use_pallas=True):
    if use_pallas:
        return ssd_scan(xdt, a, Bm, Cm, chunk=chunk)
    return ref.ssd_scan_ref(xdt, a, Bm, Cm)


@partial(jax.jit, static_argnames=("use_pallas",))
def greedy_decision(c_link, c_next, c_node, f_err, adj, *, use_pallas=True):
    if use_pallas:
        return offload_greedy(c_link, c_next, c_node, f_err, adj)
    return ref.offload_greedy_ref(c_link, c_next, c_node, f_err, adj)


@partial(jax.jit, static_argnames=("use_pallas",))
def greedy_decision_batched(c_link, c_next, c_node, f_err, adj, *,
                            use_pallas=True):
    """All T rounds of the Theorem-3 rule in one program: every operand
    carries a leading time axis (c_link (T,n,n); c_next, c_node, f_err
    (T,n); adj (T,n,n))."""
    if use_pallas:
        return offload_greedy_batched(c_link, c_next, c_node, f_err, adj)
    return jax.vmap(ref.offload_greedy_ref)(c_link, c_next, c_node, f_err, adj)


@partial(jax.jit, static_argnames=("use_pallas",))
def greedy_edges_batched(c_link, c_next, c_node, f_err, adj, *,
                         use_pallas=True):
    """Theorem-3 rule for all T rounds with COO edge emission: returns
    fixed-shape (T·n,) ``(t, src, dst, keep)`` arrays (keep=False marks
    discard rows) plus the (T, n) choice map — the sparse-MovementPlan
    feed that skips the dense (T, n, n) share tensor entirely."""
    if use_pallas:
        return offload_greedy_edges(c_link, c_next, c_node, f_err, adj)
    choice, best_j, _ = jax.vmap(ref.offload_greedy_ref)(
        c_link, c_next, c_node, f_err, adj)
    T, n = choice.shape
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (T, n), 0).reshape(-1)
    src = jax.lax.broadcasted_iota(jnp.int32, (T, n), 1).reshape(-1)
    flat = choice.reshape(-1)
    dst = jnp.where(flat == 1, best_j.reshape(-1), src)
    return t_idx, src, dst, flat != 2, choice


@partial(jax.jit, static_argnames=("k",))
def topk_neighbors(c_link, c_next, adj, *, k=2):
    """Top-k cheapest offload targets per (t, i): masked min-plus over
    out-neighbors, returned as (costs (T,n,k'), dst (T,n,k')) in
    ascending cost order with k' = min(k, n). k=1 reproduces the
    kernel's (best_cost, best_j); larger k feeds repair-style next-best
    fallbacks without a re-solve.

    Rows whose out-degree is below k are padded with (inf, -1): the
    effective per-row k is clamped to the degree, so downstream
    placement can never route to the arbitrary indices ``lax.top_k``
    reports for all-masked ties."""
    T, n = c_next.shape
    kk = min(k, n)
    eff = c_link + c_next[:, None, :]
    eye = jnp.eye(n, dtype=bool)
    eff = jnp.where(adj & ~eye[None], eff, jnp.inf)
    neg, idx = jax.lax.top_k(-eff, kk)
    cost = -neg
    return cost, jnp.where(jnp.isfinite(cost), idx, -1)


def topk_neighbors_csr(c_link_e, c_next, indptr, indices, live, *, k=2):
    """CSR-input generalization of :func:`topk_neighbors` — the O(E)
    path for edge-cost traces. ``c_link_e`` (T, E) per-edge costs over
    the lex-sorted support (``indptr``/``indices``), ``live`` (T, E)
    per-round edge liveness (schedule replay). Returns (costs
    (T,n,k'), dst (T,n,k')) with k' = min(k, max degree), ascending,
    padded with (inf, -1) — identical selection and tie-breaking to the
    dense variant on gathered costs (support order is dst order).

    Host-side prep builds a (n, maxdeg) padded edge-id table (numpy);
    the reduction itself is one jit'd program."""
    indptr = np.asarray(indptr)
    deg = np.diff(indptr)
    n = deg.shape[0]
    E = int(indptr[-1])
    maxdeg = max(int(deg.max()) if n else 0, 1)
    pad = np.full((n, maxdeg), -1, np.int64)
    slot = np.arange(maxdeg)[None, :] < deg[:, None]
    pad[slot] = np.arange(E)
    kk = min(k, maxdeg)
    return _topk_csr_core(jnp.asarray(c_link_e), jnp.asarray(c_next),
                          jnp.asarray(indices), jnp.asarray(live),
                          jnp.asarray(pad), k=kk)


@partial(jax.jit, static_argnames=("k",))
def _topk_csr_core(c_link_e, c_next, indices, live, pad, *, k):
    T = c_next.shape[0]
    n, maxdeg = pad.shape
    safe = jnp.maximum(pad, 0)
    dstp = indices[safe]                          # (n, maxdeg)
    eff = c_link_e[:, safe] + c_next[:, dstp]     # (T, n, maxdeg)
    valid = (pad >= 0)[None] & live[:, safe]
    eff = jnp.where(valid, eff, jnp.inf)
    neg, pidx = jax.lax.top_k(-eff, k)
    cost = -neg
    dst = jnp.take_along_axis(
        jnp.broadcast_to(dstp[None], (T, n, maxdeg)), pidx, axis=2)
    return cost, jnp.where(jnp.isfinite(cost), dst, -1)


@partial(jax.jit, static_argnames=("num_segments", "use_pallas"))
def segment_sum(data, segment_ids, *, num_segments, use_pallas=None):
    """out[s] = Σ data[segment_ids == s] over (E,) edge data.

    ``use_pallas=None`` takes the Pallas one-hot kernel on accelerators
    when E ≥ PALLAS_MIN_N and ``num_segments`` ≤ PALLAS_MAX_SEGMENTS —
    the kernel then makes at most 8 passes over the elements, so every
    call finishes in O(E) — and the fused jnp scatter (bitwise oracle)
    otherwise."""
    if use_pallas is None:
        use_pallas = _segment_kernel_default(data.shape[0], num_segments)
    if use_pallas:
        return segment_sum_pallas(data, segment_ids, num_segments)
    return jax.ops.segment_sum(jnp.asarray(data, jnp.float32),
                               segment_ids, num_segments=num_segments)


def segment_sum_rows(data, segment_ids, *, num_segments,
                     use_pallas=None):
    """out[s] = Σ data[segment_ids == s] over (E, ...) ROW data — the
    ND-payload sibling of :func:`segment_sum` for reducing per-row
    gradient/loss contributions onto their owning segment (the ragged
    scenario-bucket engine reduces chunk-row gradients onto the flat
    (S·n) device axis this way). Left unjitted so it inlines into the
    caller's trace. CPU path is the jnp scatter-add, which applies
    updates in row-index order — per-segment accumulation order is the
    row order, independent of how many rows other segments own (the
    property the ragged engine's in-bucket-equals-alone bitwise
    guarantee rests on). The Pallas one-hot kernel covers the
    flat (E,) case only; ND payloads flatten through it column-wise
    when it is forced on."""
    data = jnp.asarray(data, jnp.float32)
    if use_pallas is None:
        use_pallas = False          # scatter path is the bitwise oracle
    if use_pallas and data.ndim > 1:
        cols = data.reshape(data.shape[0], -1)
        out = jnp.stack([
            segment_sum_pallas(cols[:, j], segment_ids, num_segments)
            for j in range(cols.shape[1])], axis=1)
        return out.reshape((num_segments,) + data.shape[1:])
    if use_pallas:
        return segment_sum_pallas(data, segment_ids, num_segments)
    return jax.ops.segment_sum(data, segment_ids,
                               num_segments=num_segments)


@partial(jax.jit, static_argnames=("num_segments", "use_pallas"))
def segment_max(data, segment_ids, *, num_segments, use_pallas=None):
    """out[s] = max data[segment_ids == s] (−inf for empty segments);
    same kernel dispatch rule as :func:`segment_sum`."""
    if use_pallas is None:
        use_pallas = _segment_kernel_default(data.shape[0], num_segments)
    if use_pallas:
        return segment_max_pallas(data, segment_ids, num_segments)
    return jax.ops.segment_max(jnp.asarray(data, jnp.float32),
                               segment_ids, num_segments=num_segments)
