"""The paper's data-movement optimization (5)–(9).

Decision variables per round t: ``s[t,i,j]`` — fraction of data collected
at device i offloaded to device j (``s[t,i,i]`` = processed locally);
``r[t,i]`` — fraction discarded. Conservation: r + Σ_j s = 1 (eq. 8);
graph support (eq. 7); node/link capacities (eq. 9).

Solvers:

* ``greedy_linear``   — Theorem 3 closed form for the linear discard cost
  f_i(t)·D_i(t)·r_i(t): each datapoint takes the least-marginal-cost option
  among {process: c_i(t), offload→k: c_ik(t)+c_k(t+1), discard: f_i(t)}
  with k = argmin_j c_ij(t)+c_j(t+1) over out-neighbors. Implemented as
  one batched min-plus reduction over all T rounds (vectorized numpy by
  default; the Pallas ``kernels/offload_greedy`` kernel as the large-n
  accelerator backend). ``greedy_linear_loop`` keeps the original
  per-(t, i) Python loop as oracle/baseline.
* ``repair_capacities`` — Theorem 6's guidance: when expected violations
  are few, locally repair the greedy solution (cap link transfers, spill
  overflow to the node's next-best option) instead of a full re-solve.
* ``solve_convex``    — the general convex program with the 1/√G_i error
  cost (Lemma 1), via masked-softmax parametrization + Adam in pure JAX
  (interior-point-free; n·T can reach 10⁴+ variables). Capacities enter
  as quadratic hinge penalties.
* ``theorem4_closed_form`` — hierarchical-topology closed form (Thm 4).

Every solver takes the network as either a static ``adj`` matrix, a
(T, n, n) stack, or a :class:`repro.core.schedule.NetworkSchedule`
(the :func:`repro.core.schedule.as_schedule` adapter makes the three
interchangeable; static-``adj`` call sites are bitwise identical to the
pre-schedule paths, and a constant schedule never materializes the
(T, n, n) adjacency). ``realize_plan`` confronts a plan with the
network that actually happened: transfers over links absent at their
round (down, or an endpoint churned out) AND transfers whose receiver
churns out at t+1 — the arrival round — are lost in transit. Plan-once
and predictive plans are realized this way; oracle GREEDY plans pass
through unchanged because ``greedy_linear`` is receiver-aware (convex
plans price per-round adjacency only and may shed receiver-side
shares at realization).

All solvers return a :class:`MovementPlan`. Its core is SPARSE: a
COO-style edge list ``(t, src, dst, qty)`` holding only realized
transfers — the fog setting is large-n and the plans the solvers emit
touch O(T·n) edges, so materializing the dense ``(T, n, n)`` tensor
dominated wall time and memory at n ≥ 512. The dense ``.s`` view is a
lazy property kept for the oracles/tests; ``greedy_linear``,
``repair_capacities``, ``plan_cost`` (and ``data/pipeline``'s
``apply_movement``) all operate on edges, with at most O(n²) reused
per-round scratch. ``plan_cost`` evaluates the paper's objective
decomposition (process / transfer / discard-error), which
benchmarks/table3..table4 consume.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import monitoring
from repro.core.costs import CostTraces, EdgeCostTraces
from repro.core.schedule import as_schedule


@dataclasses.dataclass
class PlanEdges:
    """COO movement edges, lexicographically sorted by (t, src, dst).

    ``qty`` is the fraction of D_src(t) routed src→dst (src == dst means
    processed locally). At most one edge per (t, src, dst)."""

    t: np.ndarray    # (E,) int64
    src: np.ndarray  # (E,) int64
    dst: np.ndarray  # (E,) int64
    qty: np.ndarray  # (E,) float64

    def __len__(self) -> int:
        return len(self.t)


def _edges_from_dense(s: np.ndarray) -> PlanEdges:
    tt, ii, jj = np.nonzero(s)           # np.nonzero is lex-sorted
    return PlanEdges(t=tt.astype(np.int64), src=ii.astype(np.int64),
                     dst=jj.astype(np.int64), qty=np.asarray(s[tt, ii, jj],
                                                             np.float64))


class MovementPlan:
    """Movement decisions for all rounds.

    Sparse core: ``edges`` (COO, see :class:`PlanEdges`) plus the dense
    discard vector ``r`` (T, n). The dense ``(T, n, n)`` share tensor
    ``.s`` is a lazily materialized property — only the dense loop
    oracles and small-n tests should touch it; solver/benchmark hot
    paths stay on the edge representation.

    Construct either from a dense tensor (``MovementPlan(s=s, r=r)``,
    edges extracted lazily) or directly from edges
    (``MovementPlan(r=r, edges=edges, n=n)``).
    """

    def __init__(self, s: np.ndarray | None = None,
                 r: np.ndarray | None = None, *,
                 edges: PlanEdges | None = None, n: int | None = None):
        if r is None:
            raise TypeError("MovementPlan requires r")
        self.r = np.asarray(r)
        if s is not None:
            s = np.asarray(s)
            self._dense: np.ndarray | None = s
            self._edges: PlanEdges | None = edges
            self._n = s.shape[2]
        elif edges is not None:
            if n is None:
                raise TypeError("edge-constructed MovementPlan requires n")
            self._dense = None
            self._edges = edges
            self._n = int(n)
        else:
            raise TypeError("MovementPlan requires s or edges")
        self._splits: np.ndarray | None = None

    # -- representation views ------------------------------------------

    @property
    def n(self) -> int:
        return self._n

    @property
    def T(self) -> int:
        return self.r.shape[0]

    @property
    def edges(self) -> PlanEdges:
        if self._edges is None:
            self._edges = _edges_from_dense(self._dense)
        return self._edges

    @property
    def s(self) -> np.ndarray:
        """Dense (T, n, n) view — materialized lazily and cached.

        Oracle/test convenience only: O(T·n²) memory."""
        if self._dense is None:
            e = self._edges
            s = np.zeros((self.T, self._n, self._n))
            np.add.at(s, (e.t, e.src, e.dst), e.qty)
            self._dense = s
        return self._dense

    def _round_splits(self) -> np.ndarray:
        if self._splits is None:
            self._splits = np.searchsorted(self.edges.t,
                                           np.arange(self.T + 1))
        return self._splits

    def round_edges(self, t: int):
        """(src, dst, qty) views of round t's edges (sorted by src, dst)."""
        sp = self._round_splits()
        e = self.edges
        sl = slice(sp[t], sp[t + 1])
        return e.src[sl], e.dst[sl], e.qty[sl]

    def round_dense(self, t: int, out: np.ndarray | None = None
                    ) -> np.ndarray:
        """Round t as a dense (n, n) matrix, written into ``out`` when
        given (zeroed first) so per-round consumers can reuse a single
        buffer instead of materializing (T, n, n)."""
        if out is None:
            out = np.zeros((self._n, self._n))
        else:
            out[:] = 0.0
        src, dst, qty = self.round_edges(t)
        out[src, dst] = qty
        return out

    def diag(self) -> np.ndarray:
        """s_ii(t) for all rounds as a dense (T, n) array."""
        e = self.edges
        loc = e.src == e.dst
        d = np.zeros((self.T, self._n))
        d[e.t[loc], e.src[loc]] = e.qty[loc]
        return d

    def offload_fraction(self) -> np.ndarray:
        """Σ_{j≠i} s_ij(t) as a dense (T, n) array (edge reduction)."""
        e = self.edges
        off = e.src != e.dst
        out = np.zeros((self.T, self._n))
        np.add.at(out, (e.t[off], e.src[off]), e.qty[off])
        return out

    # -- paper quantities ----------------------------------------------

    def processed(self, D: np.ndarray) -> np.ndarray:
        """G[t,i] = s_ii(t)·D_i(t) + Σ_{j≠i} s_ji(t-1)·D_j(t-1)  (eq. 6)."""
        T, n = self.r.shape
        e = self.edges
        G = self.diag() * D
        off = e.src != e.dst
        te, se, de, qe = e.t[off], e.src[off], e.dst[off], e.qty[off]
        arrive = te + 1 < T                   # arrives at t+1, in-horizon
        np.add.at(G, (te[arrive] + 1, de[arrive]),
                  qe[arrive] * D[te[arrive], se[arrive]])
        return G

    def check(self, adj, atol: float = 1e-5):
        """Validate nonnegativity, conservation (eq. 8) and graph
        support (eq. 7). ``adj`` may be a static (n, n) matrix, a
        (T, n, n) stack or a NetworkSchedule — every offload edge is
        validated against the adjacency of ITS round, so plans that
        follow a time-varying network validate correctly (a single
        static matrix describes only one round and wrongly rejects
        plans that were valid round-by-round)."""
        T, n = self.r.shape
        sched = as_schedule(adj, T)
        e = self.edges
        assert np.all(e.qty >= -atol) and np.all(self.r >= -atol)
        total = self.r.copy()
        np.add.at(total, (e.t, e.src), e.qty)
        assert np.allclose(total, 1.0, atol=1e-4), total
        for t in range(T):
            src, dst, qty = self.round_edges(t)
            off = src != dst
            if not off.any():
                continue
            present = sched.has_edges(t, src[off], dst[off])
            lost = qty[off] * ~present
            assert np.all(lost <= atol), \
                f"offload over missing link at round {t}"


def plans_equal(p: MovementPlan, q: MovementPlan) -> bool:
    """Bitwise plan equality: COO edges and the discard vector. The
    single guard behind the benches' "modes coincide bitwise" rows and
    the representation-equivalence tests — grow it alongside
    MovementPlan so every guard stays honest."""
    e, f = p.edges, q.edges
    return (np.array_equal(e.t, f.t) and np.array_equal(e.src, f.src)
            and np.array_equal(e.dst, f.dst)
            and np.array_equal(e.qty, f.qty)
            and np.array_equal(p.r, q.r))


def no_movement_plan(T: int, n: int) -> MovementPlan:
    """Setting A: offloading and discarding disabled (G_i = D_i)."""
    tt = np.repeat(np.arange(T, dtype=np.int64), n)
    ii = np.tile(np.arange(n, dtype=np.int64), T)
    edges = PlanEdges(t=tt, src=ii, dst=ii, qty=np.ones(T * n))
    return MovementPlan(r=np.zeros((T, n)), edges=edges, n=n)


def _adj_t(adj, T: int) -> np.ndarray:
    """(T, n, n) adjacency view for the dense oracles — a broadcast view
    (no copy) for static matrices / constant schedules, materialized for
    genuinely time-varying schedules."""
    return as_schedule(adj, T).adj_view()


# ---------------------------------------------------------------------------
# Theorem 3: greedy for linear discard cost
# ---------------------------------------------------------------------------


# dispatch to the Pallas min-plus kernel above this n (accelerators only;
# on CPU the kernel runs in interpret mode and vectorized numpy wins)
PALLAS_MIN_N = 256


def _plan_from_choice(choice: np.ndarray, k: np.ndarray) -> MovementPlan:
    """(T, n) 3-way decisions + best-neighbor indices -> bang-bang plan.

    Emits COO edges directly — one edge per non-discarding (t, i) — so
    the greedy path never allocates the (T, n, n) share tensor."""
    T, n = choice.shape
    tt, ii = np.nonzero(choice != 2)         # lex-sorted by (t, src)
    dst = np.where(choice[tt, ii] == 1, k[tt, ii], ii)
    r = np.zeros((T, n))
    r[choice == 2] = 1.0
    edges = PlanEdges(t=tt.astype(np.int64), src=ii.astype(np.int64),
                      dst=dst.astype(np.int64), qty=np.ones(len(tt)))
    return MovementPlan(r=r, edges=edges, n=n)


def greedy_linear(traces: CostTraces, adj, *,
                  backend: str = "auto") -> MovementPlan:
    """Theorem 3 rule as one batched min-plus over all T rounds.

    ``adj``: static (n, n) matrix, (T, n, n) stack or NetworkSchedule —
    with a time-varying schedule each round's decision uses the
    adjacency of THAT round, i.e. the plan replans on every network
    event for free (churn-masked schedules stop offloading to exited
    nodes; flapped links drop out of the candidate set).

    backend: "numpy" (vectorized, default), "jnp" / "pallas" (device
    batched kernel via ``kernels.ops.greedy_decision_batched``), or
    "auto" (pallas on accelerators when n ≥ PALLAS_MIN_N and tileable).

    Receiver-side awareness: when the schedule carries a non-trivial
    active trace, data offloaded at t is processed by the receiver at
    t+1 — so devices inactive at t+1 leave the round-t candidate set
    (their arrivals would be lost in transit; see ``realize_plan``).
    Schedules without churn (raw matrices, stacks, constant/flap
    schedules) are bitwise unaffected.
    """
    with monitoring.span("plan.greedy") as sp:
        plan = _greedy_linear(traces, adj, backend)
        sp.count(edges=len(plan.edges.t))
    return plan


def _greedy_linear(traces: CostTraces, adj, backend: str) -> MovementPlan:
    if isinstance(traces, EdgeCostTraces):
        return greedy_linear_edges(traces, adj)
    T, n = traces.c_node.shape
    sched = as_schedule(adj, T)
    if backend == "auto":
        backend = ("pallas" if jax.default_backend() != "cpu"
                   and n >= PALLAS_MIN_N and n % 128 == 0 else "numpy")
    if backend in ("jnp", "pallas"):
        return _greedy_linear_device(traces, sched,
                                     use_pallas=backend == "pallas")
    # row-vectorized min-plus with a single reused (n, n) buffer: never
    # materializes the (T, n, n) effective-cost tensor (fresh-page writes
    # dominate wall time at fog scale), and the buffer stays cache-hot
    static = sched.static_adj
    act = sched.activity()
    inact = ~act if not act.all() else None  # receiver churn, any storage
    per_round = static is None or inact is not None
    c_next = np.concatenate([traces.c_node[1:], traces.c_node[-1:]])
    dg = np.arange(n)
    eye = np.eye(n, dtype=bool)
    invalid = None if per_round else ~static | eye
    inv_buf = np.empty((n, n), bool) if per_round else None
    k = np.zeros((T, n), np.int64)
    off_cost = np.full((T, n), np.inf)   # T-1: no off-horizon offloading
    buf = np.empty((n, n))
    for t in range(T - 1):
        np.add(traces.c_link[t], c_next[t][None, :], out=buf)
        if invalid is None:              # time-varying graph, reuse bufs
            np.logical_not(static if static is not None
                           else sched.adj_at(t), out=inv_buf)
            np.logical_or(inv_buf, eye, out=inv_buf)
            if inact is not None:        # receiver gone at arrival t+1
                np.logical_or(inv_buf, inact[t + 1][None, :], out=inv_buf)
            buf[inv_buf] = np.inf
        else:
            buf[invalid] = np.inf
        k[t] = buf.argmin(axis=1)                          # best neighbor
        off_cost[t] = buf[dg, k[t]]
    choice = np.argmin(
        np.stack([traces.c_node, off_cost, traces.f_err]), axis=0)
    return _plan_from_choice(choice, k)


def _support_live(etraces: EdgeCostTraces, sched) -> np.ndarray:
    """(T, E) liveness of the cost-support edges under the schedule —
    the sparse replacement for per-round dense adjacency rows. O(T·E)
    bool; edge-list schedules never touch a dense view, dense-mode
    schedules fall back to ``adj_at`` gathers (small-n equivalence)."""
    T, n = etraces.c_node.shape
    live = np.zeros((T, etraces.E), bool)
    if getattr(sched, "storage", None) == "edgelist":
        iu, idx = sched.union_csr()
        usrc = np.repeat(np.arange(n, dtype=np.int64), np.diff(iu))
        umap = etraces.edge_ids(usrc, idx)   # union eid -> support eid
        for t in range(T):
            ids = umap[sched.edge_ids_at(t)]
            live[t, ids[ids >= 0]] = True
    else:
        esrc = etraces.src
        for t in range(T):
            a = np.asarray(sched.adj_at(t), bool)
            live[t] = a[esrc, etraces.indices]
    return live


def _segment_min_csr(eff: np.ndarray, indptr: np.ndarray,
                     esrc: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """First-occurrence segment min over CSR rows: per-row minimum of
    ``eff`` and the edge id achieving it (−1 for rows with no finite
    entry). First-min tie-breaking in lex (dst) order — exactly
    ``argmin`` over a dense row restricted to the support."""
    n = indptr.shape[0] - 1
    E = eff.shape[0]
    rowmin = np.full(n, np.inf)
    rowarg = np.full(n, -1, np.int64)
    if E == 0:
        return rowmin, rowarg
    starts = np.minimum(indptr[:-1], E - 1)
    mins = np.minimum.reduceat(eff, starts)
    nonempty = indptr[:-1] < indptr[1:]
    rowmin[nonempty] = mins[nonempty]
    finite = np.isfinite(rowmin)
    # first edge per row attaining the min (positions ascend within rows)
    cand = np.nonzero(np.isfinite(eff) & (eff == rowmin[esrc]))[0]
    rows, first = np.unique(esrc[cand], return_index=True)
    rowarg[rows] = cand[first]
    rowmin[~finite] = np.inf
    rowarg[~finite] = -1
    return rowmin, rowarg


def greedy_linear_edges(etraces: EdgeCostTraces, adj) -> MovementPlan:
    """Theorem 3 greedy on the sparse edge support — O(T·E) end to end.

    The per-round candidate reduction is a first-occurrence segment min
    over the support CSR instead of a dense (n, n) argmin, so the plan
    is bitwise-equal to ``greedy_linear`` on the gathered dense costs
    (same float arithmetic, same lex tie-breaking) while never touching
    an (n, n) array. Receiver-aware exactly like the dense path:
    devices inactive at the arrival round t+1 leave round t's candidate
    set."""
    T, n = etraces.c_node.shape
    sched = as_schedule(adj, T)
    indices, indptr, esrc = etraces.indices, etraces.indptr, etraces.src
    act = sched.activity()
    recv = act[1:] if not act.all() else None
    notself = esrc != indices
    live_all = _support_live(etraces, sched)
    c_next = np.concatenate([etraces.c_node[1:], etraces.c_node[-1:]])
    k = np.zeros((T, n), np.int64)
    off_cost = np.full((T, n), np.inf)   # T-1: no off-horizon offloading
    eff = np.empty(etraces.E)
    for t in range(T - 1):
        np.add(etraces.c_link[t], c_next[t][indices], out=eff)
        dead = ~(live_all[t] & notself)
        if recv is not None:             # receiver gone at arrival t+1
            dead |= ~recv[t][indices]
        eff[dead] = np.inf
        rowmin, rowarg = _segment_min_csr(eff, indptr, esrc)
        off_cost[t] = rowmin
        k[t] = np.where(rowarg >= 0, indices[np.maximum(rowarg, 0)], 0)
    choice = np.argmin(
        np.stack([etraces.c_node, off_cost, etraces.f_err]), axis=0)
    return _plan_from_choice(choice, k)


def _greedy_linear_device(traces: CostTraces, adj, *,
                          use_pallas: bool) -> MovementPlan:
    from repro.kernels import ops

    T, n = traces.c_node.shape
    adj3 = np.array(_adj_t(adj, T), dtype=bool)   # kernel-side copy
    adj3[T - 1] = False    # no off-horizon offloading in the final round
    act = as_schedule(adj, T).activity()
    if not act.all():      # receivers gone at arrival t+1 leave the set
        adj3[:T - 1] &= act[1:, None, :]
    c_next = np.concatenate([traces.c_node[1:], traces.c_node[-1:]])
    # device-side COO emission: fixed-shape (T·n,) edge arrays from the
    # kernel, packed into the sparse plan without a dense (T, n, n) stop
    t_idx, src, dst, keep, _ = ops.greedy_edges_batched(
        jnp.asarray(traces.c_link, jnp.float32),
        jnp.asarray(c_next, jnp.float32),
        jnp.asarray(traces.c_node, jnp.float32),
        jnp.asarray(traces.f_err, jnp.float32),
        jnp.asarray(adj3), use_pallas=use_pallas)
    keep = np.asarray(keep)
    r = np.zeros((T, n))
    r.reshape(-1)[~keep] = 1.0
    edges = PlanEdges(t=np.asarray(t_idx)[keep].astype(np.int64),
                      src=np.asarray(src)[keep].astype(np.int64),
                      dst=np.asarray(dst)[keep].astype(np.int64),
                      qty=np.ones(int(keep.sum())))
    return MovementPlan(r=r, edges=edges, n=n)


def greedy_linear_scalar(traces: CostTraces, adj) -> MovementPlan:
    """Textbook pure-Python nested-loop Theorem-3 rule: one interpreter
    iteration per (t, i, j). The interpreter-bound baseline the batched
    min-plus replaces — benchmark reference only."""
    T, n = traces.c_node.shape
    adj3 = _adj_t(adj, T)
    s = np.zeros((T, n, n))
    r = np.zeros((T, n))
    for t in range(T):
        for i in range(n):
            best_j, best_off = -1, np.inf
            if t < T - 1:
                for j in range(n):
                    if j == i or not adj3[t, i, j]:
                        continue
                    c = traces.c_link[t, i, j] + traces.c_node[t + 1, j]
                    if c < best_off:
                        best_j, best_off = j, c
            proc = traces.c_node[t, i]
            disc = traces.f_err[t, i]
            if proc <= best_off and proc <= disc:
                s[t, i, i] = 1.0
            elif best_off <= disc:
                s[t, i, best_j] = 1.0
            else:
                r[t, i] = 1.0
    return MovementPlan(s=s, r=r)


def greedy_linear_loop(traces: CostTraces, adj) -> MovementPlan:
    """Original per-round Python loop — kept as the oracle for the
    vectorized path and the baseline in the engine_throughput bench."""
    T, n = traces.c_node.shape
    adj3 = _adj_t(adj, T)
    s = np.zeros((T, n, n))
    r = np.zeros((T, n))
    for t in range(T):
        c_next = traces.c_node[min(t + 1, T - 1)]          # c_j(t+1)
        eff = traces.c_link[t] + c_next[None, :]           # (n, n): i -> j
        eff = np.where(adj3[t], eff, np.inf)
        if t == T - 1:
            eff[:] = np.inf    # offloaded data could not be processed in-horizon
        np.fill_diagonal(eff, np.inf)
        k = np.argmin(eff, axis=1)                         # best neighbor
        off_cost = eff[np.arange(n), k]
        proc_cost = traces.c_node[t]
        disc_cost = traces.f_err[t]
        choice = np.argmin(np.stack([proc_cost, off_cost, disc_cost]), axis=0)
        for i in range(n):
            if choice[i] == 0:
                s[t, i, i] = 1.0
            elif choice[i] == 1:
                s[t, i, k[i]] = 1.0
            else:
                r[t, i] = 1.0
    return MovementPlan(s=s, r=r)


def _repair_round(s_t, r_t, prev, t, T, adj_t, traces, D, diag_next,
                  dg, eye):
    """Repair one round in place on the dense (n, n) buffer ``s_t``.

    Exactly the arithmetic of the dense vectorized repair (which is
    bitwise-equal to ``repair_capacities_loop``): vectorized violation
    detection, scalar replay of spill events in the oracle's order.
    ``adj_t`` is round t's (n, n) adjacency; ``prev`` is round t−1
    post-repair (None at t=0); ``diag_next`` is the PRE-repair s_ii of
    round t+1 (rounds ahead are untouched when round t is repaired, so
    the original plan diagonal is the oracle value)."""
    n = s_t.shape[0]
    Dt = D[t]
    Dt_safe = np.maximum(Dt, 1e-12)
    # local processing this round from s_ii(t) plus arrivals from t-1
    if t > 0:
        vol_prev = prev * D[t - 1][:, None]
        arrivals = vol_prev.sum(0) - vol_prev[dg, dg]
    else:
        arrivals = np.zeros(n)
    # (1) link capacity
    viol = (adj_t & ~eye) & (s_t * Dt[:, None] > traces.cap_link[t])
    if viol.any():
        spill_ij = np.where(
            viol, s_t - traces.cap_link[t] / Dt_safe[:, None], 0.0)
        s_t -= spill_ij
        for i, j in zip(*np.nonzero(spill_ij > 0)):   # source-major
            _revert(s_t, r_t, t, i, spill_ij[i, j], traces, Dt, arrivals)
    # (2) node capacity of receivers at t+1 (arrivals processed then)
    # violation detection is vectorized; the cut sequence per
    # overloaded receiver replicates the original sender scan so the
    # arithmetic (and therefore every knife-edge capacity
    # comparison in _revert) matches the loop oracle bit for bit
    if t + 1 < T:
        vol = s_t * Dt[:, None]
        inc = vol.sum(0) - vol[dg, dg]
        over = inc + diag_next * D[t + 1] - traces.cap_node[t + 1]
        for j in np.nonzero(over > 1e-9)[0]:
            excess = over[j]
            for i in np.nonzero(vol[:, j] > 0)[0]:
                if i == j:
                    continue
                if excess <= 1e-12:
                    break
                cut = min(vol[i, j], excess)
                spill = cut / max(Dt[i], 1e-12)
                s_t[i, j] -= spill
                excess -= cut
                _revert(s_t, r_t, t, i, spill, traces, Dt, arrivals)
    # (3) own node capacity at t for s_ii
    over = s_t[dg, dg] * Dt + arrivals - traces.cap_node[t]
    mask = over > 1e-9
    if mask.any():
        cut = np.minimum(s_t[dg, dg] * Dt, np.maximum(over, 0.0))
        spill = np.where(mask, cut / Dt_safe, 0.0)
        s_t[dg, dg] -= spill
        r_t += spill


def repair_capacities(plan: MovementPlan, traces: CostTraces,
                      adj, D: np.ndarray) -> MovementPlan:
    """Local repair of capacity violations (Theorem 6 guidance).

    Forward pass over t (sequential — arrivals chain rounds together),
    STREAMED over the sparse plan: each round is expanded into one of
    two reused dense (n, n) scratch buffers (current round + previous
    round for arrivals), repaired with the vectorized-detection /
    scalar-replay rule of :func:`_repair_round`, and re-compressed to
    edges. ``adj`` may be a static matrix, a (T, n, n) stack or a
    NetworkSchedule (per-round adjacency, no (T, n, n) materialization
    for constant/event schedules). Never materializes the (T, n, n)
    tensor, yet remains bitwise-equal to ``repair_capacities_dense``
    and ``repair_capacities_loop`` (fractional convex plans included).
    """
    with monitoring.span("plan.repair"):
        T, n = plan.r.shape
        sched = as_schedule(adj, T)
        r = plan.r.copy()
        dg = np.arange(n)
        eye = np.eye(n, dtype=bool)
        diag0 = plan.diag()      # pre-repair s_ii, read one round ahead
        cur = np.zeros((n, n))
        prev = np.zeros((n, n))
        ts, srcs, dsts, qtys = [], [], [], []
        for t in range(T):
            plan.round_dense(t, out=cur)
            _repair_round(cur, r[t], prev if t > 0 else None, t, T,
                          sched.adj_at(t), traces, D,
                          diag0[t + 1] if t + 1 < T else None, dg, eye)
            ii, jj = np.nonzero(cur)
            ts.append(np.full(len(ii), t, np.int64))
            srcs.append(ii.astype(np.int64))
            dsts.append(jj.astype(np.int64))
            qtys.append(cur[ii, jj].copy())
            prev, cur = cur, prev    # repaired round feeds t+1 arrivals
        edges = PlanEdges(t=np.concatenate(ts), src=np.concatenate(srcs),
                          dst=np.concatenate(dsts), qty=np.concatenate(qtys))
        return MovementPlan(r=r, edges=edges, n=n)


def repair_capacities_dense(plan: MovementPlan, traces: CostTraces,
                            adj, D: np.ndarray) -> MovementPlan:
    """Dense-tensor repair (the pre-sparse vectorized path) — preserved
    as the oracle/baseline for the streamed sparse ``repair_capacities``
    and the ``movement_scale`` benchmark."""
    T, n = plan.r.shape
    adj3 = _adj_t(adj, T)
    s = plan.s.copy()
    r = plan.r.copy()
    dg = np.arange(n)
    eye = np.eye(n, dtype=bool)
    for t in range(T):
        _repair_round(s[t], r[t], s[t - 1] if t > 0 else None, t, T,
                      adj3[t], traces, D,
                      s[t + 1][dg, dg] if t + 1 < T else None, dg, eye)
    return MovementPlan(s=s, r=r)


def _revert(s_t, r_t, t, i, spill, traces, Dt, arrivals):
    """Send a spilled fraction back to i's next-best option (operates on
    round t's dense (n, n) view ``s_t`` and discard row ``r_t``)."""
    cap_left = traces.cap_node[t, i] - (s_t[i, i] * Dt[i] + arrivals[i])
    if (traces.c_node[t, i] <= traces.f_err[t, i]
            and cap_left >= spill * Dt[i]):
        s_t[i, i] += spill
    else:
        r_t[i] += spill


def repair_capacities_loop(plan: MovementPlan, traces: CostTraces,
                           adj, D: np.ndarray) -> MovementPlan:
    """Original per-(i, j) Python-loop repair — oracle for the
    vectorized path."""
    T, n = plan.r.shape
    adj3 = _adj_t(adj, T)
    s = plan.s.copy()
    r = plan.r.copy()
    for t in range(T):
        Dt = D[t]
        arrivals = (s[t - 1] * D[t - 1][:, None]).sum(0) - \
            np.diag(s[t - 1]) * D[t - 1] if t > 0 else np.zeros(n)
        for i in range(n):
            for j in np.nonzero(adj3[t][i])[0]:
                if i == j or s[t, i, j] == 0:
                    continue
                cap = traces.cap_link[t, i, j]
                if s[t, i, j] * Dt[i] > cap:
                    spill = s[t, i, j] - cap / max(Dt[i], 1e-12)
                    s[t, i, j] -= spill
                    _revert(s[t], r[t], t, i, spill, traces, Dt, arrivals)
        if t + 1 < T:
            inc = (s[t] * Dt[:, None]).sum(0) - np.diag(s[t]) * Dt
            local_next = np.diag(s[t + 1]) * D[t + 1]
            over = inc + local_next - traces.cap_node[t + 1]
            for j in np.nonzero(over > 1e-9)[0]:
                senders = [i for i in range(n)
                           if i != j and s[t, i, j] * Dt[i] > 0]
                excess = over[j]
                for i in senders:
                    if excess <= 1e-12:
                        break
                    vol = s[t, i, j] * Dt[i]
                    cut = min(vol, excess)
                    spill = cut / max(Dt[i], 1e-12)
                    s[t, i, j] -= spill
                    excess -= cut
                    _revert(s[t], r[t], t, i, spill, traces, Dt, arrivals)
        G_now = np.diag(s[t]) * Dt + arrivals
        over = G_now - traces.cap_node[t]
        for i in np.nonzero(over > 1e-9)[0]:
            cut = min(np.diag(s[t])[i] * Dt[i], over[i])
            spill = cut / max(Dt[i], 1e-12)
            s[t, i, i] -= spill
            r[t, i] += spill
    return MovementPlan(s=s, r=r)


# ---------------------------------------------------------------------------
# Plan realization + edge-native repair under time-varying networks
# ---------------------------------------------------------------------------


def realize_plan(plan: MovementPlan, schedule) -> MovementPlan:
    """Confront a plan with the network that actually materialized.

    Two loss channels, both charged to the discard vector (the data
    plane never delivers the share, so its cost is the discard error,
    not a transfer):

    * **send-side** — the link is absent at the edge's round (flapped
      down, or an endpoint churned out under a masked schedule);
    * **receiver-side** — the link was up at t but the RECEIVER churns
      out by t+1, the round its arrivals would be processed: the data
      is lost in transit with the exiting node.

    A GREEDY plan solved against the schedule itself passes through
    unchanged (``greedy_linear`` is receiver-aware); a convex plan may
    shed small shares receiver-side even when solved on the true
    schedule — ``solve_convex`` prices per-round adjacency only, so
    realization is what brings its accounting back to what the data
    plane delivers. A static schedule is a bitwise pass-through for
    any plan. This is how every scheduled plan is brought back to the
    TRUE network in the ``network_dynamics`` / ``network_prediction``
    benches."""
    T, n = plan.r.shape
    sched = as_schedule(schedule, T)
    e = plan.edges
    keep = np.ones(len(e), bool)
    r = plan.r.copy()
    sp = plan._round_splits()
    for t in range(T):
        sl = slice(sp[t], sp[t + 1])
        src, dst, qty = e.src[sl], e.dst[sl], e.qty[sl]
        off = src != dst
        if not off.any():
            continue
        present = np.zeros(len(src), bool)
        present[off] = sched.has_edges(t, src[off], dst[off])
        lost = off & ~present
        if t + 1 < T:                    # arrival round: receiver gone
            act_next = np.asarray(sched.active_at(t + 1), bool)
            lost |= off & ~act_next[dst]
        if lost.any():
            np.add.at(r[t], src[lost], qty[lost])
            keep[np.arange(sp[t], sp[t + 1])[lost]] = False
    edges = PlanEdges(t=e.t[keep], src=e.src[keep], dst=e.dst[keep],
                      qty=e.qty[keep])
    return MovementPlan(r=r, edges=edges, n=n)


def repair_capacities_edges(plan: MovementPlan, traces: CostTraces,
                            adj, D: np.ndarray, *,
                            k: int = 4) -> MovementPlan:
    """Edge-native capacity repair with next-best offload fallbacks.

    Streams the sparse plan round by round as (src, dst, qty) edge
    dicts plus O(n) aggregates — no dense per-round (n, n) scratch is
    ever rebuilt. Violation handling differs from the Theorem-6 oracle
    rule (:func:`repair_capacities` / ``repair_capacities_dense``) in
    one way: when a transfer overruns a link or receiver capacity, the
    spilled share first tries the source's next-cheapest feasible
    neighbors — the k-best min-plus candidates from
    ``kernels.ops.topk_neighbors`` — respecting both link and receiver
    headroom, before falling back to the oracle's local-process /
    discard rule. Saturated-but-connected networks therefore keep more
    data in play instead of discarding it. Feasible plans pass through
    bitwise unchanged.
    """
    T, n = plan.r.shape
    sched = as_schedule(adj, T)
    kk = max(1, min(k, n - 1))
    sparse_costs = isinstance(traces, EdgeCostTraces)
    topk: tuple | None = None

    def _topk():
        """k-best min-plus candidates, solved LAZILY on the first spill:
        feasible plans pass through without paying the device transfer
        or the top-k program. Dense CostTraces run the batched (T,n,n)
        solve (no asymptotic memory added); EdgeCostTraces run the CSR
        variant on (T, E) costs + schedule liveness — no dense
        adjacency view is ever requested, so edge-list schedules repair
        above the dense size guard."""
        nonlocal topk
        if topk is None:
            from repro.kernels import ops

            c_next = np.concatenate([traces.c_node[1:],
                                     traces.c_node[-1:]])
            if sparse_costs:
                live = _support_live(traces, sched)
                live &= traces.src != traces.indices
                cc, cd = ops.topk_neighbors_csr(
                    np.asarray(traces.c_link, np.float32),
                    np.asarray(c_next, np.float32),
                    traces.indptr, traces.indices, live, k=kk)
            else:
                cc, cd = ops.topk_neighbors(
                    jnp.asarray(traces.c_link, jnp.float32),
                    jnp.asarray(c_next, jnp.float32),
                    jnp.asarray(sched.adj_view()), k=kk)
            topk = (np.asarray(cc), np.asarray(cd))
        return topk

    diag0 = plan.diag()                  # pre-repair s_ii one round ahead
    r = plan.r.copy()
    arrivals = np.zeros(n)
    ts, srcs, dsts, qtys = [], [], [], []
    for t in range(T):
        src, dst, qty = plan.round_edges(t)
        share: dict[tuple[int, int], float] = {}
        for i, j, q in zip(src, dst, qty):
            share[(int(i), int(j))] = share.get((int(i), int(j)), 0.0) \
                + float(q)
        Dt = D[t]
        cap_link_t = traces.cap_link[t]
        if sparse_costs:
            def _cl(i, j):
                """Per-edge link capacity (0 for off-support pairs)."""
                eid = traces.edge_ids([i], [j])[0]
                return float(cap_link_t[eid]) if eid >= 0 else 0.0
        else:
            def _cl(i, j):
                return cap_link_t[i, j]
        local_next = diag0[t + 1] * D[t + 1] if t + 1 < T else None
        inc = np.zeros(n)
        for (i, j), q in share.items():
            if i != j:
                inc[j] += q * Dt[i]

        def _place(i, frac):
            """Route a spilled fraction of D_i(t): next-best neighbors
            (link + receiver headroom), then local, then discard."""
            if t + 1 < T:
                cand_cost, cand = _topk()
                for c in range(kk):
                    if frac <= 1e-12:
                        return
                    cost = cand_cost[t, i, c]
                    j2 = int(cand[t, i, c])
                    if not np.isfinite(cost) or j2 < 0:
                        break            # ascending order: rest invalid
                    cur_q = share.get((i, j2), 0.0)
                    head = min(
                        _cl(i, j2) - cur_q * Dt[i],
                        traces.cap_node[t + 1, j2] - local_next[j2]
                        - inc[j2])
                    put = min(frac, head / max(Dt[i], 1e-12))
                    if put <= 1e-12:
                        continue
                    share[(i, j2)] = cur_q + put
                    inc[j2] += put * Dt[i]
                    frac -= put
            if frac > 1e-12:             # oracle fallback (_revert rule)
                cap_left = traces.cap_node[t, i] - (
                    share.get((i, i), 0.0) * Dt[i] + arrivals[i])
                if (traces.c_node[t, i] <= traces.f_err[t, i]
                        and cap_left >= frac * Dt[i]):
                    share[(i, i)] = share.get((i, i), 0.0) + frac
                else:
                    r[t, i] += frac

        # (1) link capacities (snapshot the keys; re-read quantities —
        # _place may have grown an edge processed later in the sweep)
        for i, j in sorted(k_ for k_ in share if k_[0] != k_[1]):
            q = share[(i, j)]
            if q > 0.0 and q * Dt[i] > _cl(i, j):
                spill = q - _cl(i, j) / max(Dt[i], 1e-12)
                share[(i, j)] = q - spill
                inc[j] -= spill * Dt[i]
                _place(i, spill)
        # (2) receiver node capacities at t+1 (arrivals processed then)
        if t + 1 < T:
            for j in range(n):
                excess = inc[j] + local_next[j] - traces.cap_node[t + 1, j]
                if excess <= 1e-9:
                    continue
                for i, j_ in sorted(k_ for k_ in share
                                    if k_[1] == j and k_[0] != j):
                    if excess <= 1e-12:
                        break
                    q = share[(i, j)]
                    if q <= 0.0:
                        continue
                    cut = min(q * Dt[i], excess)
                    spill = cut / max(Dt[i], 1e-12)
                    share[(i, j)] = q - spill
                    inc[j] -= cut
                    excess -= cut
                    _place(i, spill)
        # (3) own node capacity at t for s_ii
        for i in range(n):
            loc = share.get((i, i), 0.0)
            over = loc * Dt[i] + arrivals[i] - traces.cap_node[t, i]
            if over > 1e-9:
                cut = min(loc * Dt[i], max(over, 0.0))
                spill = cut / max(Dt[i], 1e-12)
                share[(i, i)] = loc - spill
                r[t, i] += spill

        arrivals[:] = 0.0                # repaired round feeds t+1
        for (i, j), q in share.items():
            if i != j and q > 0.0:
                arrivals[j] += q * Dt[i]
        items = sorted((ij, q) for ij, q in share.items() if q > 0.0)
        ts.append(np.full(len(items), t, np.int64))
        srcs.append(np.array([ij[0] for ij, _ in items], np.int64))
        dsts.append(np.array([ij[1] for ij, _ in items], np.int64))
        qtys.append(np.array([q for _, q in items], np.float64))
    edges = PlanEdges(t=np.concatenate(ts), src=np.concatenate(srcs),
                      dst=np.concatenate(dsts), qty=np.concatenate(qtys))
    return MovementPlan(r=r, edges=edges, n=n)


# ---------------------------------------------------------------------------
# General convex solver (1/sqrt error cost, Lemma 1)
# ---------------------------------------------------------------------------


def _convex_mask(traces: CostTraces, adj) -> np.ndarray:
    """Support mask over the [s_ij | r_i] softmax parametrization."""
    T, n = traces.c_node.shape
    adj3 = _adj_t(adj, T)
    mask = np.concatenate(
        [adj3 | np.eye(n, dtype=bool)[None], np.ones((T, n, 1), bool)],
        axis=2).copy()                                     # [s_ij | r_i]
    # no off-horizon offloading in the final round
    mask[T - 1, :, :n] &= np.eye(n, dtype=bool)
    return mask


def _convex_core(c_node, c_link, f_err, cap_node, cap_link, mask_j, Dj, z0,
                 *, error_model, gamma, iters, lr, capacity_penalty):
    """One scenario's Adam descent, pure jnp — vmap-able over a leading
    scenario axis for batched sweeps."""
    n = c_node.shape[1]

    def unpack(z):
        z = jnp.where(mask_j, z, -jnp.inf)
        p = jax.nn.softmax(z, axis=2)                      # rows sum to 1
        s = p[:, :, :n]
        r = p[:, :, n]
        return s, r

    def G_of(s):
        G = jnp.einsum("tii,ti->ti", s, Dj)
        s_off = s * (1.0 - jnp.eye(n))[None]
        inc = jnp.einsum("tji,tj->ti", s_off, Dj)
        return G.at[1:].add(inc[:-1])

    def objective(z):
        s, r = unpack(z)
        G = G_of(s)
        off = s * (1 - jnp.eye(n))[None]
        proc = jnp.sum(G * c_node)
        trans = jnp.sum(off * Dj[:, :, None] * c_link)
        if error_model == "sqrt":
            err = jnp.sum(f_err * gamma / jnp.sqrt(G + 1e-3))
        elif error_model == "neg_G":
            err = -jnp.sum(f_err * G)
        else:  # "discard"
            err = jnp.sum(f_err * Dj * r)
        pen = (jnp.sum(jax.nn.relu(G - cap_node) ** 2)
               + jnp.sum(jax.nn.relu(off * Dj[:, :, None] - cap_link) ** 2))
        return proc + trans + err + capacity_penalty * pen

    grad_fn = jax.grad(objective)

    def step(carry, i):
        z, m, v = carry
        g = grad_fn(z)
        g = jnp.where(mask_j, g, 0.0)
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        mh = m / (1 - 0.9 ** (i + 1))
        vh = v / (1 - 0.999 ** (i + 1))
        z = z - lr * mh / (jnp.sqrt(vh) + 1e-8)
        return (z, m, v), None

    (z, _, _), _ = jax.lax.scan(
        step, (z0, jnp.zeros_like(z0), jnp.zeros_like(z0)),
        jnp.arange(iters))
    return unpack(z)


@partial(jax.jit, static_argnames=("error_model", "gamma", "iters", "lr",
                                   "capacity_penalty", "batched"))
def _convex_run(c_node, c_link, f_err, cap_node, cap_link, mask, D, z0, *,
                error_model, gamma, iters, lr, capacity_penalty, batched):
    core = partial(_convex_core, error_model=error_model, gamma=gamma,
                   iters=iters, lr=lr, capacity_penalty=capacity_penalty)
    if batched:
        core = jax.vmap(core)
    return core(c_node, c_link, f_err, cap_node, cap_link, mask, D, z0)


def _convex_inputs(traces: CostTraces, adj, D: np.ndarray):
    return (jnp.asarray(traces.c_node), jnp.asarray(traces.c_link),
            jnp.asarray(traces.f_err),
            jnp.asarray(np.minimum(traces.cap_node, 1e12)),
            jnp.asarray(np.minimum(traces.cap_link, 1e12)),
            jnp.asarray(_convex_mask(traces, adj)),
            jnp.asarray(D, jnp.float32))


def solve_convex(traces: CostTraces, adj, D: np.ndarray, *,
                 error_model: str = "sqrt", gamma: float = 1.0,
                 iters: int = 800, lr: float = 0.05,
                 capacity_penalty: float = 50.0,
                 seed: int = 0) -> MovementPlan:
    """Masked-softmax parametrization of [s | r] + Adam (pure JAX).

    error_model: "sqrt" (f·γ/√G), "neg_G" (−f·G), "discard" (f·D·r).
    ``adj`` may be a static matrix, a (T, n, n) stack or a
    NetworkSchedule (the support mask then varies per round).
    """
    T, n = traces.c_node.shape
    z0 = 0.01 * jax.random.normal(jax.random.PRNGKey(seed), (T, n, n + 1))
    s, r = _convex_run(*_convex_inputs(traces, adj, D), z0,
                       error_model=error_model, gamma=gamma, iters=iters,
                       lr=lr, capacity_penalty=capacity_penalty,
                       batched=False)
    return MovementPlan(s=np.asarray(s, float), r=np.asarray(r, float))


def solve_convex_batched(traces_seq, adj_seq, D_seq, *,
                         error_model: str = "sqrt", gamma: float = 1.0,
                         iters: int = 800, lr: float = 0.05,
                         capacity_penalty: float = 50.0,
                         seeds=0) -> list[MovementPlan]:
    """Solve many (traces, adj, D) scenarios in ONE vmapped program.

    All scenarios must share (T, n). ``seeds`` is an int — the SAME z0
    init for every scenario, matching what sequential
    ``solve_convex(..., seed=seeds)`` calls would use — or a sequence
    of per-scenario seeds for decorrelated restarts. Scenario b
    reproduces ``solve_convex(traces_seq[b], ..., seed=seeds[b])`` up
    to vmap-reduction reassociation.
    """
    B = len(traces_seq)
    T, n = traces_seq[0].c_node.shape
    if np.ndim(seeds) == 0:
        seeds = [int(seeds)] * B
    stacked = [jnp.stack(a) for a in zip(*(
        _convex_inputs(tr, adj, D)
        for tr, adj, D in zip(traces_seq, adj_seq, D_seq)))]
    z0 = jnp.stack([0.01 * jax.random.normal(jax.random.PRNGKey(sd),
                                             (T, n, n + 1))
                    for sd in seeds])
    s, r = _convex_run(*stacked, z0, error_model=error_model, gamma=gamma,
                       iters=iters, lr=lr, capacity_penalty=capacity_penalty,
                       batched=True)
    return [MovementPlan(s=np.asarray(s[b], float),
                         r=np.asarray(r[b], float)) for b in range(B)]


# ---------------------------------------------------------------------------
# Theorem 4: hierarchical closed form
# ---------------------------------------------------------------------------


def theorem4_closed_form(c: np.ndarray, c_server: float, c_t: float,
                         gamma: float, D: np.ndarray
                         ) -> tuple[np.ndarray, np.ndarray]:
    """n devices offloading to an edge server (node n+1).

    Returns (r*, s*) per eqs. (13)-(14):
      r_i* = 1 − (γ/2c_i)^{2/3}/D_i − s_i,
      s_i* = (γ/(2(c_{n+1}+c_t)))^{2/3} / Σ_j D_j.
    """
    s_star = (gamma / (2 * (c_server + c_t))) ** (2.0 / 3.0) / D.sum()
    s = np.full_like(c, s_star)
    r = 1.0 - (gamma / (2 * c)) ** (2.0 / 3.0) / D - s
    return np.clip(r, 0.0, 1.0), np.clip(s, 0.0, 1.0)


# ---------------------------------------------------------------------------
# Objective evaluation (Tables III / IV)
# ---------------------------------------------------------------------------


def plan_cost(plan: MovementPlan, traces: CostTraces, D: np.ndarray, *,
              error_model: str = "discard", gamma: float = 1.0) -> dict:
    """Objective decomposition on the sparse plan: the transfer term and
    moved-rate reduce over realized edges only (no (T, n, n) pages)."""
    T, n = plan.r.shape
    G = plan.processed(D)
    e = plan.edges
    off = e.src != e.dst
    te, se, de, qe = e.t[off], e.src[off], e.dst[off], e.qty[off]
    proc = float(np.sum(G * traces.c_node))
    if isinstance(traces, EdgeCostTraces):
        eids = traces.edge_ids(se, de)       # plan edges live on support
        c_edge = np.where(eids >= 0,
                          traces.c_link[te, np.maximum(eids, 0)], 0.0)
        trans = float(np.sum(qe * D[te, se] * c_edge))
    else:
        trans = float(np.sum(qe * D[te, se] * traces.c_link[te, se, de]))
    if error_model == "sqrt":
        disc = float(np.sum(traces.f_err * gamma / np.sqrt(G + 1e-3)))
    elif error_model == "neg_G":
        disc = float(-np.sum(traces.f_err * G))
    else:
        disc = float(np.sum(traces.f_err * D * plan.r))
    total_data = float(D.sum())
    total = proc + trans + disc
    off_frac = plan.offload_fraction()          # Σ_{j≠i} s_ij as (T, n)
    return {"process": proc, "transfer": trans, "discard": disc,
            "total": total,
            "unit": total / max(total_data, 1e-9),
            "data_total": total_data,
            "moved_rate": float((off_frac * D).sum() / max(D.sum(), 1e-9)
                                + (plan.r * D).sum() / max(D.sum(), 1e-9)),
            "processed_frac": float(G.sum() / max(D.sum(), 1e-9)),
            "discarded_frac": float((plan.r * D).sum() / max(D.sum(), 1e-9))}
