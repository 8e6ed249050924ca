"""Fog data pipeline (paper §V-A):

* per-device Poisson arrivals, mean |D_V|/(nT) per round
* i.i.d. (uniform w/o replacement from the global pool) or non-i.i.d.
  (each device restricted to a random 5 of 10 labels) collection
* application of a MovementPlan to the physical sample streams: offloaded
  samples travel one round (arrive at t+1), discarded samples vanish —
  this is the data plane matching movement.py's decision plane. Routing
  follows the plan's SPARSE edges (``apply_movement``;
  ``apply_movement_dense`` is the preserved dense-row oracle).
"""
from __future__ import annotations

import dataclasses
import warnings

import numpy as np

from repro.core import monitoring
from repro.core.movement import MovementPlan


@dataclasses.dataclass
class FogStreams:
    """collected[t][i] -> (idx array of global sample ids)."""

    collected: list[list[np.ndarray]]
    n: int
    T: int


@dataclasses.dataclass
class FlatStreams:
    """Array-backed sample streams — the O(samples) representation the
    sparse network plane stages at device counts where ``FogStreams``'
    T×n Python lists of tiny arrays are unaffordable. Sample ``s`` is
    held by device ``dev[s]`` at round ``t[s]`` with global dataset id
    ``idx[s]``; rows are lex-sorted by (t, dev). Convert with
    :func:`flat_from_streams` / :func:`streams_from_flat` (small n)."""

    t: np.ndarray       # (N,) int64 round of each sample
    dev: np.ndarray     # (N,) int64 holding device
    idx: np.ndarray     # (N,) int64 global dataset id
    n: int
    T: int

    def cell_key(self) -> np.ndarray:
        return self.t * np.int64(self.n) + self.dev


def _flat_sorted(t, dev, idx, n: int, T: int) -> FlatStreams:
    t = np.asarray(t, np.int64)
    dev = np.asarray(dev, np.int64)
    idx = np.asarray(idx, np.int64)
    order = np.argsort(t * np.int64(n) + dev, kind="stable")
    return FlatStreams(t=t[order], dev=dev[order], idx=idx[order],
                       n=n, T=T)


def flat_from_streams(streams: FogStreams) -> FlatStreams:
    """Flatten a ``FogStreams`` (preserves per-cell sample order)."""
    n, T = streams.n, streams.T
    cells = [ix for row in streams.collected for ix in row]
    lens = np.fromiter((len(ix) for ix in cells), np.int64, len(cells))
    cell = np.repeat(np.arange(T * n, dtype=np.int64), lens)
    idx = (np.concatenate(cells) if cells and lens.sum()
           else np.empty(0, np.int64))
    return FlatStreams(t=cell // n, dev=cell % n,
                       idx=np.asarray(idx, np.int64), n=n, T=T)


def streams_from_flat(flat: FlatStreams) -> FogStreams:
    """Expand back to per-cell lists (small-n bridge for the oracles)."""
    n, T = flat.n, flat.T
    key = flat.cell_key()
    starts = np.searchsorted(key, np.arange(T * n + 1, dtype=np.int64))
    collected = [[flat.idx[starts[t * n + i]:starts[t * n + i + 1]].copy()
                  for i in range(n)] for t in range(T)]
    return FogStreams(collected=collected, n=n, T=T)


def poisson_streams(n: int, T: int, y: np.ndarray, *, iid: bool = True,
                    labels_per_device: int = 5, n_classes: int = 10,
                    rng: np.random.Generator | None = None,
                    mean_per_round: float | None = None) -> FogStreams:
    # foglint: disable=rng-stream-discipline -- documented default: rng=None selects the fixed legacy stream 0 (bitwise-stable staging across PRs); scenario producers pass a derived Generator
    rng = rng or np.random.default_rng(0)
    N = len(y)
    mean = mean_per_round or N / (n * T)
    device_labels = [rng.choice(n_classes, labels_per_device, replace=False)
                     for _ in range(n)]
    by_label = {c: np.nonzero(y == c)[0] for c in range(n_classes)}
    collected: list[list[np.ndarray]] = []
    for t in range(T):
        row = []
        for i in range(n):
            k = rng.poisson(mean)
            if iid:
                idx = rng.choice(N, size=min(k, N), replace=False)
            else:
                pool = np.concatenate([by_label[c] for c in device_labels[i]])
                idx = rng.choice(pool, size=min(k, len(pool)), replace=False)
            row.append(idx.astype(np.int64))
        collected.append(row)
    return FogStreams(collected=collected, n=n, T=T)


def poisson_streams_flat(n: int, T: int, y: np.ndarray, *,
                         rng: np.random.Generator | None = None,
                         mean_per_round: float | None = None
                         ) -> FlatStreams:
    """Vectorized i.i.d. Poisson arrivals as a :class:`FlatStreams` —
    the O(samples) producer for large n (one ``rng.poisson`` draw for
    the whole (T, n) grid, one ``rng.integers`` draw for the sample
    ids; with-replacement i.i.d. sampling, unlike the per-cell
    without-replacement draw of :func:`poisson_streams`, so the two
    producers are distribution-equal, not bitwise twins)."""
    # foglint: disable=rng-stream-discipline -- documented default: rng=None selects the fixed legacy stream 0 (bitwise-stable staging across PRs); scenario producers pass a derived Generator
    rng = rng or np.random.default_rng(0)
    N = len(y)
    mean = mean_per_round or N / (n * T)
    k = rng.poisson(mean, (T, n)).astype(np.int64)
    total = int(k.sum())
    cell = np.repeat(np.arange(T * n, dtype=np.int64), k.reshape(-1))
    idx = rng.integers(0, N, total, dtype=np.int64)
    return FlatStreams(t=cell // n, dev=cell % n, idx=idx, n=n, T=T)


def counts(streams) -> np.ndarray:
    """D[t,i] = |D_i(t)| (FogStreams or FlatStreams)."""
    if isinstance(streams, FlatStreams):
        return counts_flat(streams)
    return np.array([[len(ix) for ix in row] for row in streams.collected],
                    dtype=float)


def counts_flat(flat: FlatStreams) -> np.ndarray:
    """(T, n) per-cell sample counts of a flat stream — the per-device
    gather of held shares, computed through the segment-sum kernel
    dispatch (``kernels.ops.segment_sum``: jnp scatter on CPU and for
    more cells than the kernel's segment bound, Pallas one-hot kernel
    on accelerators otherwise)."""
    from repro.kernels import ops
    N = flat.idx.shape[0]
    if N == 0:
        return np.zeros((flat.T, flat.n))
    c = ops.segment_sum(np.ones(N, np.float32),
                        flat.cell_key().astype(np.int32),
                        num_segments=flat.T * flat.n)
    return np.asarray(c, np.float64).reshape(flat.T, flat.n)


def apply_movement(streams: FogStreams, plan: MovementPlan,
                   rng: np.random.Generator | None = None
                   ) -> list[list[np.ndarray]]:
    """Route physical samples per the plan.

    Returns processed[t][i] — global sample ids device i processes at
    round t (= retained local share + arrivals offloaded at t−1).
    Fractions are realized by randomized rounding of contiguous splits.

    Operates on the plan's sparse edges: the (n+1,) share rows are
    rebuilt from the edges and ``r`` a block of cells at a time, so
    routing never touches the dense (T, n, n) tensor yet stays
    bitwise-identical to ``apply_movement_dense`` (the preserved
    oracle), generator state included — the rebuilt row IS the dense
    row.
    """
    with monitoring.span("prep.route") as sp:
        processed, collected, kept = _route_cells(streams, plan, rng)
        sp.count(collected=collected, processed=kept)
    return processed


# share rows built per block of cells: the (cells, n+1) float64 scratch
# stays under 8 MB whatever n is
_ROUTE_BLOCK = 1 << 20


def _route_cells(streams: FogStreams, plan: MovementPlan, rng):
    """:func:`apply_movement` without its span; also returns the
    samples collected and the samples processed.

    Array work over the horizon; only the permutations stay per cell:
    one ``rng.permutation`` per nonempty (t, i) cell, in (t, i) order,
    so the generator is drawn exactly as a per-cell loop draws it. Each
    cell's (n+1) share row (edges, then ``r``) is clipped, divided by
    its whole row's sum, cumsummed and floored — the loop's arithmetic
    on a (cells, n+1) table, row for row. Every nonempty part gets its
    destination cell ((t, i) kept, (t+1, j) offloaded; offloads past
    the horizon and the tail beyond the last cut are discarded) and one
    stable sort by destination orders a cell as arrivals from t−1 by
    source, then its own retained part. The cells returned are views of
    one array."""
    # foglint: disable=rng-stream-discipline -- documented default: rng=None selects fixed stream 1 (kept distinct from the collection stream); callers on the scenario path pass a derived Generator
    rng = rng or np.random.default_rng(1)
    n, T = streams.n, streams.T
    cells = [ix for row in streams.collected for ix in row]
    lens = np.fromiter((len(ix) for ix in cells), np.int64, T * n)
    perms = [rng.permutation(ix) for ix in cells if len(ix)]
    ids = np.concatenate(perms) if perms else np.empty(0, np.int64)
    first = np.cumsum(lens) - lens                  # cell starts in ids
    e = plan.edges
    ekey = e.t * np.int64(n) + e.src                # sorted: (t, src, dst)
    r = np.asarray(plan.r).reshape(-1)
    src, size, dest = [], [], []
    block = max(1, _ROUTE_BLOCK // (n + 1))
    for c0 in range(0, T * n, block):
        c1 = min(c0 + block, T * n)
        lo, hi = np.searchsorted(ekey, (c0, c1))
        rows = np.zeros((c1 - c0, n + 1))
        rows[ekey[lo:hi] - c0, e.dst[lo:hi]] = e.qty[lo:hi]
        rows[:, n] = r[c0:c1]
        np.clip(rows, 0, None, out=rows)
        rows /= np.maximum(rows.sum(1), 1e-12)[:, None]
        ends = np.floor(np.cumsum(rows[:, :n], 1) * lens[c0:c1, None]
                        + 1e-9).astype(np.int64)
        sizes = np.diff(ends, axis=1, prepend=0)
        cc, jj = np.nonzero(sizes)                  # (cell, j) order
        c = cc + c0
        own = jj == c % n
        keep = own | (c < (T - 1) * n)              # t + 1 < T
        cc, jj, c, own = cc[keep], jj[keep], c[keep], own[keep]
        sz = sizes[cc, jj]
        src.append(first[c] + ends[cc, jj] - sz)
        size.append(sz)
        dest.append(np.where(own, c, (c // n + 1) * n + jj))
    src, size, dest = (np.concatenate(a) for a in (src, size, dest))
    order = np.argsort(dest, kind="stable")
    size = size[order]
    at = np.concatenate(([0], np.cumsum(size)))     # part starts in out
    out = ids[np.repeat(src[order] - at[:-1], size)
              + np.arange(at[-1], dtype=np.int64)]
    bounds = at[np.searchsorted(dest[order], np.arange(T * n + 1))]
    processed = [[out[bounds[c]:bounds[c + 1]]
                  if bounds[c + 1] > bounds[c] else np.empty(0, np.int64)
                  for c in range(t * n, t * n + n)] for t in range(T)]
    return processed, int(lens.sum()), int(at[-1])


def apply_movement_flat(flat: FlatStreams, plan: MovementPlan,
                        rng: np.random.Generator | None = None
                        ) -> FlatStreams:
    """Route a flat stream per a BANG-BANG plan — O(samples + plan
    edges), never touching per-cell Python lists.

    Bang-bang means every (t, i) share row moves, keeps or discards its
    WHOLE collection: each share row holds at most one qty-1 edge
    (keep-all is the self-edge, move-all an off-diagonal one) and the
    discard vector ``r`` is 0 on rows with an edge and {0, 1} elsewhere
    — exactly what ``greedy_linear`` emits. Routing is then a gather
    ``dev' = route[t, dev]``: offloaded samples arrive at t+1,
    ``route = −1`` discards, moves past the horizon vanish. Membership
    per cell is identical to :func:`apply_movement` (whole cells move,
    so the per-cell permutation is irrelevant); within-cell sample
    order follows collection order, not the dense path's permuted
    order. Fractional plans fall back to the dense-oracle path through
    the stream converters (small n only)."""
    with monitoring.span("prep.route") as sp:
        out = _route_flat(flat, plan, rng)
        sp.count(collected=int(flat.idx.shape[0]),
                 processed=int(out.idx.shape[0]))
    return out


def _route_flat(flat: FlatStreams, plan: MovementPlan, rng) -> FlatStreams:
    n, T = flat.n, flat.T
    r = np.asarray(plan.r)
    route = np.full((T, n), -1, np.int64)   # no edge, no retain: discard
    bang = bool(np.isin(r, (0.0, 1.0)).all())
    for t in range(T):
        if not bang:
            break
        src, dst, qty = plan.round_edges(t)
        on = qty >= 0.5
        if (qty.size and (np.unique(src[on]).size < on.sum()
                          or not np.isin(qty, (0.0, 1.0)).all()
                          or r[t, src[on]].any())):
            bang = False
            break
        route[t, src[on]] = dst[on]
    if not bang:
        processed, _, _ = _route_cells(streams_from_flat(flat), plan, rng)
        return flat_from_streams(
            FogStreams(collected=processed, n=n, T=T))
    dev2 = route[flat.t, flat.dev]
    t2 = flat.t + (dev2 != flat.dev)
    keep = (dev2 >= 0) & (t2 < T)
    return _flat_sorted(t2[keep], dev2[keep], flat.idx[keep], n, T)


def apply_movement_dense(streams: FogStreams, plan: MovementPlan,
                         rng: np.random.Generator | None = None
                         ) -> list[list[np.ndarray]]:
    """Dense-row routing (the pre-sparse path) — preserved as the
    bitwise oracle for the edge-based ``apply_movement``."""
    # foglint: disable=rng-stream-discipline -- documented default: rng=None selects fixed stream 1 (kept distinct from the collection stream); callers on the scenario path pass a derived Generator
    rng = rng or np.random.default_rng(1)
    n, T = streams.n, streams.T
    buckets: list[list[list[np.ndarray]]] = \
        [[[] for _ in range(n)] for _ in range(T)]
    for t in range(T):
        # foglint: disable=dense-materialization -- dense-row oracle path (see docstring); the sparse twin is apply_movement_flat
        s_t, r_t = plan.s[t], plan.r[t]
        for i in range(n):
            idx = streams.collected[t][i]
            if len(idx) == 0:
                continue
            idx = rng.permutation(idx)
            fracs = np.concatenate([s_t[i], [r_t[i]]])
            fracs = np.clip(fracs, 0, None)
            fracs = fracs / max(fracs.sum(), 1e-12)
            cuts = np.floor(np.cumsum(fracs) * len(idx) + 1e-9).astype(int)
            ends = cuts[:-1]                     # last bucket = discard
            starts = np.empty_like(ends)
            starts[0] = 0
            starts[1:] = ends[:-1]
            for j in np.nonzero(ends > starts)[0]:
                part = idx[starts[j]:ends[j]]
                if j == i:
                    buckets[t][i].append(part)
                elif t + 1 < T:
                    buckets[t + 1][j].append(part)
    return [[np.concatenate(cell) if cell else np.empty(0, np.int64)
             for cell in row] for row in buckets]


def label_similarity(label_multisets: list[np.ndarray],
                     n_classes: int = 10) -> float:
    """Average pairwise multiset label overlap (paper Fig. 4b):
    s_ij = |Y_i ∩ Y_j| / min(|Y_i|, |Y_j|)."""
    hists = [np.bincount(l, minlength=n_classes) for l in label_multisets]
    sims = []
    n = len(hists)
    for i in range(n):
        for j in range(i + 1, n):
            lo = np.minimum(hists[i], hists[j]).sum()
            denom = min(hists[i].sum(), hists[j].sum())
            if denom > 0:
                sims.append(lo / denom)
    return float(np.mean(sims)) if sims else 0.0


# ---------------------------------------------------------------------------
# shape buckets: pad dimensions up to coarse buckets so a sweep of nearby
# shapes hits ONE compiled program per bucket instead of recompiling per
# point (core.engine caches programs per (model, eta, staging, bucket))
# ---------------------------------------------------------------------------

# padding-inflation warnings are deduplicated per sweep, not emitted per
# point: a 50-point sweep with one undersized bucket should warn once
_PAD_WARNED: set = set()


def reset_padding_warnings() -> None:
    """Start a new sweep: padding-inflation warnings may fire again."""
    _PAD_WARNED.clear()


def _warn_once(key, msg: str) -> None:
    if key not in _PAD_WARNED:
        _PAD_WARNED.add(key)
        warnings.warn(msg, stacklevel=3)


# padded rounds/devices still execute their (zero-weight) compute, so
# bucketing a dimension that would inflate it beyond this factor falls
# back to the exact size: nearby shapes share a program, distant ones
# pay a recompile instead of phantom FLOPs every round
BUCKET_MAX_INFLATION = 4 / 3


def bucket_size(value: int, bucket: str = "pow2", *,
                max_inflation: float | None = None) -> int:
    """Round a dimension up to its shape bucket.

    ``bucket="pow2"`` rounds up to the next power of two (so nearby
    shapes share a compiled program); ``"exact"`` is the identity.
    ``max_inflation`` caps the padding: when the pow2 bucket would grow
    the dimension beyond ``value * max_inflation`` the exact size is
    kept (used for the compute-bearing n and T axes)."""
    value = int(value)
    if bucket == "exact":
        return value
    if bucket != "pow2":
        raise ValueError(f"unknown bucket policy {bucket!r}; "
                         "expected 'pow2' or 'exact'")
    b = 1 << max(0, value - 1).bit_length()
    if max_inflation is not None and b > value * max_inflation:
        return value
    return b


def bucket_rounds(T: int, tau: int, bucket: str = "pow2") -> int:
    """Bucket for the round axis: the WINDOW count (T/tau) is bucketed,
    then scaled back by tau — so tau-aligned horizons (the common
    same-T sweep) pad zero rounds while cross-T sweeps still share a
    program per bucket. Padded windows train nothing but still execute,
    so inflation beyond ``BUCKET_MAX_INFLATION`` keeps the exact window
    count. Always a multiple of tau (the engines scan (T/tau, tau)
    aggregation windows)."""
    n_win = -(-int(T) // int(tau))
    return bucket_size(n_win, bucket,
                       max_inflation=BUCKET_MAX_INFLATION) * int(tau)


def pad_size(processed, requested: int = 0, *,
             bucket: str = "exact") -> int:
    """P for padded batches: the post-movement per-device maximum.

    Offloading concentrates data, so sizing P from the *collected*
    streams (or a too-small user override) silently drops samples at the
    receiving devices. A ``requested`` pad size only ever grows P.
    ``bucket="pow2"`` rounds the result up to its shape bucket (for the
    batched sweep engine's program cache). Accepts the per-cell lists
    or a :class:`FlatStreams`."""
    if isinstance(processed, FlatStreams):
        key = processed.cell_key()
        post_max = (int(np.bincount(key).max()) if key.size else 1) or 1
    else:
        post_max = max((len(ix) for row in processed for ix in row),
                       default=1) or 1
    if requested and requested < post_max:
        warnings.warn(
            f"max_points={requested} is below the post-movement maximum "
            f"of {post_max} samples/device/round; padding to {post_max} "
            "to avoid dropping samples", stacklevel=2)
    return bucket_size(max(requested, post_max), bucket)


def pad_batches(processed_t: list[np.ndarray], x: np.ndarray,
                y: np.ndarray, max_points: int, *,
                bucket: str = "exact"):
    """Stack per-device variable-size batches into padded arrays.

    Returns (xb (n, P, ...), yb (n, P), w (n, P) weight mask).
    ``bucket="pow2"`` pads P up to its shape bucket first."""
    n = len(processed_t)
    P = bucket_size(max_points, bucket)
    xb = np.zeros((n, P, *x.shape[1:]), x.dtype)
    yb = np.zeros((n, P), np.int32)
    w = np.zeros((n, P), np.float32)
    for i, idx in enumerate(processed_t):
        if len(idx) > P:
            warnings.warn(
                f"pad_batches: device {i} holds {len(idx)} samples but "
                f"P={P}; truncating (size P via pipeline.pad_size to "
                "avoid this)", stacklevel=2)
        k = min(len(idx), P)
        if k:
            xb[i, :k] = x[idx[:k]]
            yb[i, :k] = y[idx[:k]]
            w[i, :k] = 1.0
    return xb, yb, w


def stage_rounds(processed, y: np.ndarray, max_points: int):
    """Stage the whole horizon as dense (T, n, P) slots.

    Returns (idx (T, n, P) int32 — global sample ids, 0-padded;
    yb (T, n, P) int32; w (T, n, P) float32 weight mask;
    counts (T, n) float32). Pixels are gathered on device from these
    indices by ``core.engine``. Per-cell lists and a
    :class:`FlatStreams` stage alike, in O(samples): one pass over the
    cells, one scatter per staged array; a cell keeps its first P
    samples."""
    P = int(max_points)
    return _dense_slots(*_cells_upto(processed, P), y, P)


@dataclasses.dataclass
class ScenarioBatch:
    """S scenarios staged into ONE stacked, bucket-padded stream.

    All arrays carry a leading scenario axis: ``idx``/``yb``/``w`` are
    (S, T_b, n_b, P_b), ``counts``/``act`` are (S, T_b, n_b), ``is_agg``
    is (S, T_b). ``T``/``n``/``P`` record each scenario's TRUE dims so
    histories can be sliced back out of the padding; phantom rounds and
    devices are inactive (act 0, counts 0, is_agg False) and train
    nothing."""

    idx: np.ndarray
    yb: np.ndarray
    w: np.ndarray
    counts: np.ndarray
    act: np.ndarray
    is_agg: np.ndarray
    T: list[int]
    n: list[int]
    P: list[int]
    tau: int

    @property
    def dims(self) -> tuple[int, int, int, int]:
        """(S, T_b, n_b, P_b) — the bucket the program compiles for."""
        return self.idx.shape


# chunk size of the ragged row tables: each (round, device) cell is cut
# into ceil(count/RAGGED_CHUNK) virtual rows of RAGGED_CHUNK sample
# slots, so the compiled per-round work is proportional to the actual
# sample total (plus at most one partially-filled chunk per nonempty
# cell) instead of S·P_max. Larger chunks mean fewer rows (less
# parameter gather/scatter traffic) but more slot padding per cell.
RAGGED_CHUNK = 8


# chunk size of the scan engine's packed rows (stage_rounds_scan).
# At the paper's CNN cell C=32, 64 and 128 all execute 2,048 slots a
# round; on a TPU v5e the scan program took 1,312, 652 and 370 ms a job
# against 1,230 ms on dense slots (PERF.md): larger rows batch the
# vmapped convolutions into fewer, larger groups
PACKED_CHUNK = 128


@dataclasses.dataclass
class RaggedScenarioBatch:
    """S scenarios staged as per-round RAGGED chunk-row tables.

    Instead of the dense (S, T_b, n_b, P_b) slab of
    :class:`ScenarioBatch` — whose phantom P-slots still execute — each
    round carries a flat table of ``R_b`` chunk rows of ``chunk``
    sample slots: row r of round t holds up to ``chunk`` samples of ONE
    (scenario, device) cell, identified by ``cell[t, r]`` on the flat
    scenario-major device axis (``s * n_b + dev``). Phantom rows point
    at the trash segment ``S * n_b`` so their (zero-weight) garbage
    never reaches a real device. A scenario's rows are contiguous and
    ordered by (device, chunk) within each round, so its per-device
    reduction order — and therefore its bits — is the same whether it
    trains alone or inside the bucket.

    ``counts``/``act``/``is_agg`` and the true-dims lists are exactly
    the dense batch's: the device axis stays (S, n_b), only the sample
    axis goes ragged."""

    idx: np.ndarray      # (T_b, R_b, C) int32 global sample ids
    yb: np.ndarray       # (T_b, R_b, C) int32 labels
    w: np.ndarray        # (T_b, R_b, C) float32 slot mask
    cell: np.ndarray     # (T_b, R_b) int32 flat device id; S*n_b=trash
    counts: np.ndarray   # (S, T_b, n_b) float32
    act: np.ndarray      # (S, T_b, n_b) float32
    is_agg: np.ndarray   # (S, T_b) bool
    T: list[int]
    n: list[int]
    P: list[int]
    tau: int
    chunk: int
    total_samples: int   # true sample total across the bucket
    total_rows: int      # true (unpadded) chunk-row total

    @property
    def dims(self) -> tuple[int, int, int, int, int]:
        """(S, T_b, n_b, R_b, C) — the bucket the program compiles
        for."""
        S, T_b, n_b = self.counts.shape
        R_b, C = self.idx.shape[1:]
        return S, T_b, n_b, R_b, C


def _cell_table(processed):
    """Normalize per-cell lists or a :class:`FlatStreams` into
    ((T, n) sample counts, concatenated ids in (t, dev, within-cell)
    order) — the inputs the chunk-row and slot stagers scatter from —
    in one pass over the cells."""
    if isinstance(processed, FlatStreams):
        T, n = processed.T, processed.n
        lens = np.bincount(processed.cell_key(),
                           minlength=T * n).astype(np.int64).reshape(T, n)
        return lens, np.asarray(processed.idx, np.int64)
    cells = [ix for row in processed for ix in row]
    lens = np.fromiter((len(ix) for ix in cells), np.int64,
                       len(cells)).reshape(len(processed), -1)
    ids = (np.concatenate(cells).astype(np.int64, copy=False)
           if cells and lens.sum() else np.empty(0, np.int64))
    return lens, ids


def _cells_upto(processed, P: int):
    """:func:`_cell_table` with each cell cut to its first P samples
    (warning once when one holds more)."""
    lens, ids = _cell_table(processed)
    if lens.size and lens.max() > P:
        warnings.warn(
            f"staging: a device holds {int(lens.max())} samples but "
            f"P={P}; truncating", stacklevel=3)
        T, n = lens.shape
        lens_flat = lens.reshape(-1)
        cell_of = np.repeat(np.arange(T * n, dtype=np.int64), lens_flat)
        starts = np.cumsum(lens_flat) - lens_flat
        ids = ids[np.arange(ids.size, dtype=np.int64) - starts[cell_of] < P]
        lens = np.minimum(lens, P)
    return lens, ids


def _dense_slots(lens, ids, y, P: int):
    """(idx, yb, w, counts) of :func:`stage_rounds` from the cells."""
    T, n = lens.shape
    idx = np.zeros((T, n, P), np.int32)
    yb = np.zeros((T, n, P), np.int32)
    w = np.zeros((T, n, P), np.float32)
    _scatter_samples(lens, ids, y, np.arange(T * n, dtype=np.int64) * P,
                     idx, yb, w)
    return idx, yb, w, lens.astype(np.float32)


def _scatter_samples(lens, ids, y, first, idx, yb, w):
    """Write each cell's samples, in order, to consecutive flat slots of
    ``idx``/``yb``/``w`` from the cell's first slot ``first`` (flat,
    one per cell of the (T, n) ``lens``): one scatter per staged
    array."""
    if ids.size:
        lens_flat = lens.reshape(-1)
        flat = np.arange(ids.size, dtype=np.int64) + np.repeat(
            first - (np.cumsum(lens_flat) - lens_flat), lens_flat)
        idx.reshape(-1)[flat] = ids
        yb.reshape(-1)[flat] = y[ids]
        w.reshape(-1)[flat] = 1.0


def _scatter_rows(lens, ids, y, base: int, off, idx, yb, w, cell):
    """Scatter one stream's samples into chunk-row tables, in place:
    cell (t, dev) of ``lens`` becomes ceil(count / C) consecutive rows
    of round t from row ``off[t]`` on, devices in index order, each row
    labelled ``base + dev`` in ``cell``; a cell's samples fill its rows
    slot by slot. Returns the rows per round."""
    T, n = lens.shape
    R_b, C = idx.shape[1:]
    nrows = -(-lens // C)
    nr_flat = nrows.reshape(-1)
    per_round = nrows.sum(1)
    # flat row id of each cell's first row
    first = ((np.cumsum(nr_flat) - nr_flat).reshape(T, n)
             - (np.cumsum(per_round) - per_round)[:, None]
             + (np.asarray(off, np.int64)
                + np.arange(T, dtype=np.int64) * R_b)[:, None]).reshape(-1)
    _scatter_samples(lens, ids, y, first * C, idx, yb, w)
    rows = np.arange(int(nr_flat.sum()), dtype=np.int64) + np.repeat(
        first - (np.cumsum(nr_flat) - nr_flat), nr_flat)
    cell.reshape(-1)[rows] = base + np.repeat(
        np.tile(np.arange(n, dtype=np.int64), T), nr_flat)
    return per_round


def stage_rounds_scan(processed, y: np.ndarray, max_points: int):
    """Stage the horizon for the scan engine: as packed chunk rows where
    those execute fewer sample slots than the dense (T, n, P) slab of
    :func:`stage_rounds`, else as that slab — decided from the staged
    counts alone.

    Each (t, dev) cell keeps its first ``max_points`` samples, as
    :func:`stage_rounds` does. Packed, a cell becomes ceil(count / C)
    rows of ``C = PACKED_CHUNK`` slots; round t's rows are packed
    device-major into an (R, C) table with ``cell[t, r]`` the owning
    device (``n`` marks a phantom row). R is the pow2 bucket of the
    busiest round's row count, without the ``BUCKET_MAX_INFLATION``
    cap: jobs whose loads vary within 2x share one compiled program.
    The rows are taken only when T·R·C < T·n·P.

    Returns (idx, yb int32, w float32 slot mask — each (T, R, C) packed
    or (T, n, P) dense —, cell (T, R) int32 or None when dense, counts
    (T, n) float32). The dense arrays and the counts equal
    :func:`stage_rounds`'."""
    C, P = int(PACKED_CHUNK), int(max_points)
    lens, ids = _cells_upto(processed, P)
    T, n = lens.shape
    rows = (-(-lens // C)).sum(1)
    R = bucket_size(max(int(rows.max(initial=0)), 1), "pow2")
    if R * C >= n * P:
        idx, yb, w, counts = _dense_slots(lens, ids, y, P)
        return idx, yb, w, None, counts
    idx = np.zeros((T, R, C), np.int32)
    yb = np.zeros((T, R, C), np.int32)
    w = np.zeros((T, R, C), np.float32)
    cell = np.full((T, R), n, np.int32)
    _scatter_rows(lens, ids, y, 0, np.zeros(T, np.int64), idx, yb, w, cell)
    return idx, yb, w, cell, lens.astype(np.float32)


def stage_scenario_ragged(processed_list, y: np.ndarray,
                          act_list: list[np.ndarray], tau: int, *,
                          max_points: list[int] | None = None,
                          bucket: str = "pow2",
                          chunk: int | None = None
                          ) -> RaggedScenarioBatch:
    """Ragged counterpart of :func:`stage_scenario_batch`.

    Per-round chunk-row tables are built with one scatter per staged
    array (:func:`_scatter_rows`): every (scenario, round, device) cell
    becomes ceil(count/chunk) rows, rows of one
    round packed scenario-major (scenario rows contiguous, devices in
    index order — the order the in-bucket-equals-alone bitwise
    guarantee rests on), the row axis bucketed like the other compute
    axes (pow2, ``BUCKET_MAX_INFLATION`` cap). The inflation warning
    fires on the RAGGED totals — padded row-slots vs the samples
    actually staged — not on the dense pow2 P prediction, since the
    phantom P-slots the dense warning prices never execute here."""
    C = int(chunk or RAGGED_CHUNK)
    if C < 1:
        raise ValueError(f"chunk must be >= 1; got {C}")
    S = len(processed_list)
    tables = [_cell_table(p) for p in processed_list]
    T_s = [lens.shape[0] for lens, _ in tables]
    n_s = [lens.shape[1] for lens, _ in tables]
    P_s = [pad_size(p, (max_points or [0] * S)[b])
           for b, p in enumerate(processed_list)]
    T_b = max(bucket_rounds(T, tau, bucket) for T in T_s)
    n_b = max(bucket_size(n, bucket,
                          max_inflation=BUCKET_MAX_INFLATION)
              for n in n_s)
    nrows = [-(-lens // C) for lens, _ in tables]        # (T_s, n_s)
    rows_round = np.zeros(T_b, np.int64)
    for b, nr in enumerate(nrows):
        rows_round[:T_s[b]] += nr.sum(1)
    R_max = int(rows_round.max()) if T_b else 0
    R_b = bucket_size(max(R_max, 1), bucket,
                      max_inflation=BUCKET_MAX_INFLATION)
    total_rows = int(rows_round.sum())
    total_samples = int(sum(int(lens.sum()) for lens, _ in tables))
    # satellite of the dense P-inflation warning, computed on what
    # ragged staging actually executes: padded row-slots per horizon
    if total_rows and T_b * R_b > 2 * total_rows:
        _warn_once(
            ("ragged_inflation", T_b, R_b),
            f"ragged bucket pads {total_rows} chunk rows up to "
            f"{T_b}x{R_b} row slots (> 2x) for this sweep; split the "
            "sweep into finer buckets if the padded compute shows up")

    trash = S * n_b
    idx = np.zeros((T_b, R_b, C), np.int32)
    yb = np.zeros((T_b, R_b, C), np.int32)
    w = np.zeros((T_b, R_b, C), np.float32)
    cell = np.full((T_b, R_b), trash, np.int32)
    counts = np.zeros((S, T_b, n_b), np.float32)
    act = np.zeros((S, T_b, n_b), np.float32)
    is_agg = np.zeros((S, T_b), bool)
    off = np.zeros(T_b, np.int64)        # next free row per round
    for b, (lens, ids) in enumerate(tables):
        T, n = T_s[b], n_s[b]
        counts[b, :T, :n] = lens
        act[b, :T, :n] = np.asarray(act_list[b], np.float32)
        is_agg[b, :T] = (np.arange(T) + 1) % tau == 0
        off[:T] += _scatter_rows(lens, ids, y, b * n_b, off[:T], idx, yb,
                                 w, cell)
    return RaggedScenarioBatch(
        idx=idx, yb=yb, w=w, cell=cell, counts=counts, act=act,
        is_agg=is_agg, T=T_s, n=n_s, P=P_s, tau=tau, chunk=C,
        total_samples=total_samples, total_rows=total_rows)


def ragged_rows(processed_list, chunk: int | None = None) -> np.ndarray:
    """Per-round chunk-row totals a ragged bucket of these scenarios
    would stage — the cost model's work estimate, computed without
    building the tables (rows = Σ over cells of ceil(count/chunk))."""
    C = int(chunk or RAGGED_CHUNK)
    T_max = max(
        (p.T if isinstance(p, FlatStreams) else len(p))
        for p in processed_list)
    rows = np.zeros(T_max, np.int64)
    for p in processed_list:
        lens, _ = _cell_table(p)
        rows[:lens.shape[0]] += (-(-lens // C)).sum(1)
    return rows


def stage_scenario_batch(processed_list: list[list[list[np.ndarray]]],
                         y: np.ndarray,
                         act_list: list[np.ndarray], tau: int, *,
                         max_points: list[int] | None = None,
                         bucket: str = "pow2") -> ScenarioBatch:
    """Stage a whole sweep bucket for the batched engine.

    Each scenario's (T_s, n_s, P_s) stream is padded up to the shared
    shape bucket — the round axis via :func:`bucket_rounds` (window
    count bucketed, always a tau multiple), the device and sample axes
    via :func:`bucket_size` — and stacked on a leading scenario axis.
    Warns ONCE per sweep (see :func:`reset_padding_warnings`) when the
    bucket inflates a scenario's own sample budget P by more than 2x:
    that is the signal to split the sweep into finer buckets."""
    S = len(processed_list)
    T_s = [len(p) for p in processed_list]
    n_s = [len(p[0]) for p in processed_list]
    P_s = [pad_size(p, (max_points or [0] * S)[b])
           for b, p in enumerate(processed_list)]
    T_b = max(bucket_rounds(T, tau, bucket) for T in T_s)
    n_b = max(bucket_size(n, bucket,
                          max_inflation=BUCKET_MAX_INFLATION)
              for n in n_s)
    # P buckets off the GROUP max (one program per bucket either way);
    # the pow2 rounding buys cross-sweep cache hits, the cap keeps the
    # padded per-round compute bounded like the n/T axes
    P_b = bucket_size(max(P_s), bucket,
                      max_inflation=BUCKET_MAX_INFLATION)
    for b, P in enumerate(P_s):
        if P_b > 2 * P:
            _warn_once(
                ("P_inflation", P_b),
                f"shape bucket pads P={P} up to {P_b} (> 2x) for at "
                "least one scenario of this sweep; split the sweep "
                "into finer buckets if the padded compute shows up")
    idx = np.zeros((S, T_b, n_b, P_b), np.int32)
    yb = np.zeros((S, T_b, n_b, P_b), np.int32)
    w = np.zeros((S, T_b, n_b, P_b), np.float32)
    counts = np.zeros((S, T_b, n_b), np.float32)
    act = np.zeros((S, T_b, n_b), np.float32)
    is_agg = np.zeros((S, T_b), bool)
    for b, processed in enumerate(processed_list):
        T, n = T_s[b], n_s[b]
        i_b, y_b, w_b, c_b = stage_rounds(processed, y, P_b)
        idx[b, :T, :n], yb[b, :T, :n] = i_b, y_b
        w[b, :T, :n], counts[b, :T, :n] = w_b, c_b
        act[b, :T, :n] = np.asarray(act_list[b], np.float32)
        is_agg[b, :T] = (np.arange(T) + 1) % tau == 0
    return ScenarioBatch(idx=idx, yb=yb, w=w, counts=counts, act=act,
                         is_agg=is_agg, T=T_s, n=n_s, P=P_s, tau=tau)
