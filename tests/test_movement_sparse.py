"""Sparse MovementPlan core: COO edge representation round-trips with
the dense view, and the edge-based default paths (greedy emission,
streamed repair, row-reconstructing apply_movement, plan_cost) are
bitwise-equal to the preserved dense oracles — fractional convex plans
included."""
import numpy as np
import pytest

from repro.core import movement as mv
from repro.core.costs import synthetic_costs, with_capacity
from repro.core.topology import fully_connected, make_topology
from repro.data import pipeline as pl


def _fractional_plan(T, n, adj, rng):
    """Dense fractional plan: random softmax rows on the support."""
    mask = np.concatenate([(adj | np.eye(n, dtype=bool))[None].repeat(T, 0),
                           np.ones((T, n, 1), bool)], axis=2)
    z = np.where(mask, rng.standard_normal((T, n, n + 1)), -np.inf)
    p = np.exp(z - z.max(2, keepdims=True))
    p /= p.sum(2, keepdims=True)
    return mv.MovementPlan(s=p[:, :, :n].copy(), r=p[:, :, n].copy())


# ---------------------------------------------------------------------------
# representation round-trips
# ---------------------------------------------------------------------------


def test_dense_to_edges_to_dense_roundtrip():
    rng = np.random.default_rng(0)
    T, n = 5, 7
    adj = make_topology("random", n, rng, rho=0.6)
    plan = _fractional_plan(T, n, adj, rng)
    dense = plan.s.copy()
    rebuilt = mv.MovementPlan(r=plan.r, edges=plan.edges, n=n)
    np.testing.assert_array_equal(rebuilt.s, dense)
    np.testing.assert_array_equal(rebuilt.diag(), np.einsum("tii->ti", dense))


def test_edges_to_dense_to_edges_roundtrip():
    rng = np.random.default_rng(1)
    tr = synthetic_costs(9, 6, rng)
    plan = mv.greedy_linear(tr, make_topology("random", 9, rng, rho=0.5))
    e1 = plan.edges
    back = mv.MovementPlan(s=plan.s, r=plan.r)
    e2 = back.edges
    for a, b in ((e1.t, e2.t), (e1.src, e2.src), (e1.dst, e2.dst),
                 (e1.qty, e2.qty)):
        np.testing.assert_array_equal(a, b)


def test_greedy_default_path_is_edge_native():
    """The default greedy path must not materialize the dense tensor —
    the (T, n, n) pages are exactly what the sparse plane removes."""
    rng = np.random.default_rng(2)
    tr = synthetic_costs(16, 8, rng)
    plan = mv.greedy_linear(tr, fully_connected(16))
    assert plan._dense is None
    assert len(plan.edges) <= 8 * 16          # ≤ one edge per (t, i)
    repaired = mv.repair_capacities(
        plan, with_capacity(tr, cap_node=1e9, cap_link=1e9),
        fully_connected(16), np.ones((8, 16)))
    assert repaired._dense is None


def test_no_movement_plan_is_sparse_identity():
    plan = mv.no_movement_plan(4, 5)
    assert plan._dense is None
    e = plan.edges
    np.testing.assert_array_equal(e.src, e.dst)
    np.testing.assert_array_equal(plan.s, np.tile(np.eye(5)[None],
                                                  (4, 1, 1)))


def test_round_dense_and_round_edges_views():
    rng = np.random.default_rng(3)
    T, n = 6, 8
    adj = make_topology("random", n, rng, rho=0.5)
    plan = _fractional_plan(T, n, adj, rng)
    sparse = mv.MovementPlan(r=plan.r, edges=plan.edges, n=n)
    buf = np.empty((n, n))
    for t in range(T):
        np.testing.assert_array_equal(sparse.round_dense(t, out=buf),
                                      plan.s[t])
        src, dst, qty = sparse.round_edges(t)
        np.testing.assert_array_equal(qty, plan.s[t][src, dst])


def test_processed_matches_dense_einsum_oracle():
    rng = np.random.default_rng(4)
    T, n = 7, 6
    adj = make_topology("random", n, rng, rho=0.7)
    plan = _fractional_plan(T, n, adj, rng)
    D = rng.poisson(15, (T, n)).astype(float)
    s = plan.s
    G_dense = np.einsum("tii,ti->ti", s, D).astype(float).copy()
    s_off = s * (1.0 - np.eye(n))[None]
    inc = np.einsum("tji,tj->ti", s_off, D)
    G_dense[1:] += inc[:-1]
    np.testing.assert_allclose(plan.processed(D), G_dense,
                               rtol=1e-12, atol=1e-12)


# ---------------------------------------------------------------------------
# bitwise equivalence of the sparse default paths vs the dense oracles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sparse_repair_bitwise_vs_dense_and_loop_fractional(seed):
    rng = np.random.default_rng(seed)
    T, n = 6, 8
    tr = with_capacity(synthetic_costs(n, T, rng, f_err=2.0),
                       cap_node=12.0, cap_link=4.0)
    adj = make_topology("random", n, rng, rho=0.6)
    D = rng.poisson(15, (T, n)).astype(float)
    plan = _fractional_plan(T, n, adj, rng)
    got = mv.repair_capacities(plan, tr, adj, D)        # streamed sparse
    dense = mv.repair_capacities_dense(plan, tr, adj, D)
    loop = mv.repair_capacities_loop(plan, tr, adj, D)
    np.testing.assert_array_equal(got.s, dense.s)
    np.testing.assert_array_equal(got.r, dense.r)
    np.testing.assert_array_equal(got.s, loop.s)
    np.testing.assert_array_equal(got.r, loop.r)


def test_sparse_repair_bitwise_on_greedy_plans():
    rng = np.random.default_rng(7)
    T, n = 10, 12
    tr = with_capacity(synthetic_costs(n, T, rng), cap_node=20.0,
                       cap_link=8.0)
    adj = make_topology("random", n, rng, rho=0.5)
    D = rng.poisson(18, (T, n)).astype(float)
    plan = mv.greedy_linear(tr, adj)
    got = mv.repair_capacities(plan, tr, adj, D)
    want = mv.repair_capacities_dense(plan, tr, adj, D)
    np.testing.assert_array_equal(got.s, want.s)
    np.testing.assert_array_equal(got.r, want.r)


# one (n+1) share row, unnormalised, whose floor cuts at 54 samples
# differ when it is normalised by a sequential sum instead of numpy's
# pairwise sum of the whole row
_ULP_ROW = [0.24167571409434496, 0.8881183206591798, 0.22586942841732438,
            0.1245547058352835, 0.2883307570075776, 0.5861230648127328,
            0.5540905021732678, 0.8097107759127777, 0.5604759520061858,
            0.2884212144312105, 0.6840672449878391]


def _route_case(case):
    """(streams, plan) of one routing case of the oracle test."""
    rng = np.random.default_rng(11)
    n, T, mean = 6, 7, 12
    if case in ("greedy", "softmax", "discard_rows", "last_round_offload",
                "empty_cells", "zero_row", "pairwise_sum"):
        n, T, mean = 10, 12, 25
    elif case == "setting_d":
        n, T, mean = 10, 100, 60
    y = rng.integers(0, 10, 6000)
    streams = pl.poisson_streams(n, T, y, rng=rng, mean_per_round=mean)
    adj = make_topology("random", n, rng, rho=0.6)
    if case == "fractional":
        plan = _fractional_plan(T, n, adj, rng)
        return streams, mv.MovementPlan(r=plan.r, edges=plan.edges, n=n)
    if case == "softmax":
        return streams, _fractional_plan(T, n, adj, rng)
    if case == "pairwise_sum":
        plan = _fractional_plan(T, n, fully_connected(n), rng)
        s, r = plan.s.copy(), plan.r.copy()
        row = np.array(_ULP_ROW)
        s[1, 0], r[1, 0] = row[:n], row[n]
        streams.collected[1][0] = rng.choice(len(y), 54, replace=False)

        def cuts(total):
            return np.floor(np.cumsum(row / total) * 54 + 1e-9)
        assert not np.array_equal(cuts(row.sum()), cuts(np.cumsum(row)[-1]))
        return streams, mv.MovementPlan(s=s, r=r)
    if case == "setting_d":
        D = pl.counts(streams)
        tr = with_capacity(synthetic_costs(n, T, rng), float(D.mean()))
        adj = fully_connected(n)
        plan = mv.repair_capacities(mv.greedy_linear(tr, adj), tr, adj, D)
        qty = plan.edges.qty
        assert ((qty > 0) & (qty < 1)).any()       # fractional repaired rows
        return streams, plan
    tr = synthetic_costs(n, T, rng, f_err=0.4 if case == "greedy" else 0.7)
    plan = mv.greedy_linear(tr, adj)
    if case == "discard_rows":
        s, r = plan.s.copy(), plan.r.copy()
        drop = rng.random((T, n)) < 0.3
        s[drop], r[drop] = 0.0, 1.0
        plan = mv.MovementPlan(s=s, r=r)
    elif case == "last_round_offload":
        s = np.zeros((T, n, n))
        s[:, np.arange(n), (np.arange(n) + 1) % n] = 0.5
        s[:, np.arange(n), np.arange(n)] = 0.25
        plan = mv.MovementPlan(s=s, r=np.full((T, n), 0.25))
    elif case == "empty_cells":
        for t, i in zip(*np.nonzero(rng.random((T, n)) < 0.3)):
            streams.collected[t][i] = np.empty(0, np.int64)
        streams.collected[3] = [np.empty(0, np.int64)] * n
    elif case == "zero_row":
        s, r = plan.s.copy(), plan.r.copy()
        s[2, 4], r[2, 4] = 0.0, 0.0
        s[T - 1, 0], r[T - 1, 0] = 0.0, 0.0
        plan = mv.MovementPlan(s=s, r=r)
    return streams, plan


# ids "False" and "True" name the bang-bang and the fractional plan at
# n = 6; the cases after them widen the plans and the streams
@pytest.mark.parametrize("case", [
    pytest.param("bang_bang", id="False"),
    pytest.param("fractional", id="True"),
    "greedy", "softmax", "setting_d", "discard_rows",
    "last_round_offload", "empty_cells", "zero_row", "pairwise_sum"])
def test_apply_movement_bitwise_vs_dense_oracle(case):
    from repro.core import monitoring

    streams, plan = _route_case(case)
    n, T = streams.n, streams.T
    monitoring.reset()
    rng_got, rng_want = np.random.default_rng(42), np.random.default_rng(42)
    got = pl.apply_movement(streams, plan, rng_got)
    want = pl.apply_movement_dense(streams, plan, rng_want)
    for t in range(T):
        for i in range(n):
            assert got[t][i].dtype == want[t][i].dtype
            np.testing.assert_array_equal(got[t][i], want[t][i])
    # the generator is left where the per-cell loop leaves it
    assert rng_got.integers(0, 2 ** 62) == rng_want.integers(0, 2 ** 62)
    route = monitoring.totals()["prep.route"]
    assert route["collected"] == sum(len(ix) for row in streams.collected
                                     for ix in row)
    assert route["processed"] == sum(len(ix) for row in want for ix in row)
    monitoring.reset()


def test_plan_cost_matches_dense_formula():
    rng = np.random.default_rng(5)
    T, n = 6, 9
    adj = make_topology("random", n, rng, rho=0.5)
    tr = synthetic_costs(n, T, rng)
    D = rng.poisson(20, (T, n)).astype(float)
    for plan in (mv.greedy_linear(tr, adj),
                 _fractional_plan(T, n, adj, rng)):
        got = mv.plan_cost(plan, tr, D)
        s = plan.s
        off = s * (1 - np.eye(n))[None]
        want_trans = float(np.sum(off * D[:, :, None] * tr.c_link))
        want_moved = float((off.sum(2) * D).sum() / max(D.sum(), 1e-9)
                           + (plan.r * D).sum() / max(D.sum(), 1e-9))
        assert got["transfer"] == pytest.approx(want_trans, rel=1e-12)
        assert got["moved_rate"] == pytest.approx(want_moved, rel=1e-12)


def test_kernel_edge_emission_matches_choice_path():
    """ops.greedy_edges_batched must emit exactly the edges the
    choice/argmin pair implies."""
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(6)
    T, n = 4, 32
    tr = synthetic_costs(n, T, rng)
    adj3 = np.broadcast_to(make_topology("random", n, rng, rho=0.5),
                           (T, n, n)).copy()
    adj3[T - 1] = False
    c_next = np.concatenate([tr.c_node[1:], tr.c_node[-1:]])
    args = (jnp.asarray(tr.c_link, jnp.float32),
            jnp.asarray(c_next, jnp.float32),
            jnp.asarray(tr.c_node, jnp.float32),
            jnp.asarray(tr.f_err, jnp.float32), jnp.asarray(adj3))
    choice, best_j, _ = ops.greedy_decision_batched(*args,
                                                    use_pallas=False)
    t_idx, src, dst, keep, choice2 = ops.greedy_edges_batched(
        *args, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(choice), np.asarray(choice2))
    choice, best_j = np.asarray(choice), np.asarray(best_j)
    keep = np.asarray(keep)
    np.testing.assert_array_equal(keep, (choice != 2).reshape(-1))
    want_dst = np.where(choice == 1, best_j,
                        np.arange(n)[None, :]).reshape(-1)
    np.testing.assert_array_equal(np.asarray(dst)[keep], want_dst[keep])


def test_topk_neighbors_first_column_is_argmin():
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(8)
    T, n = 3, 16
    tr = synthetic_costs(n, T, rng)
    adj = make_topology("random", n, rng, rho=0.6)
    c_next = np.concatenate([tr.c_node[1:], tr.c_node[-1:]])
    costs, idx = ops.topk_neighbors(
        jnp.asarray(tr.c_link, jnp.float32),
        jnp.asarray(c_next, jnp.float32),
        jnp.asarray(np.broadcast_to(adj, (T, n, n))), k=2)
    costs, idx = np.asarray(costs), np.asarray(idx)
    assert costs.shape == (T, n, 2) and np.all(costs[..., 0] <= costs[..., 1])
    eff = tr.c_link + c_next[:, None, :]
    eff = np.where(adj[None] & ~np.eye(n, dtype=bool)[None], eff, np.inf)
    np.testing.assert_allclose(costs[..., 0], eff.min(2), rtol=1e-6)


def test_topk_neighbors_pads_low_degree_rows():
    """Regression: rows with out-degree < k must pad with (inf, -1) —
    lax.top_k reports arbitrary indices for all-masked ties, which
    placement would then treat as real neighbors."""
    import jax.numpy as jnp

    from repro.kernels import ops

    T, n, k = 2, 8, 3
    adj = np.zeros((n, n), bool)
    adj[0, 1] = adj[1, 0] = adj[1, 2] = True     # deg(0)=1, deg(1)=2
    rng = np.random.default_rng(5)
    c_link = rng.random((T, n, n))
    c_next = rng.random((T, n))
    costs, idx = ops.topk_neighbors(
        jnp.asarray(c_link, jnp.float32), jnp.asarray(c_next, jnp.float32),
        jnp.asarray(np.broadcast_to(adj, (T, n, n))), k=k)
    costs, idx = np.asarray(costs), np.asarray(idx)
    for t in range(T):
        assert idx[t, 0, 0] == 1 and np.all(idx[t, 0, 1:] == -1)
        assert np.isinf(costs[t, 0, 1:]).all()
        assert set(idx[t, 1, :2]) == {0, 2} and idx[t, 1, 2] == -1
        # isolated rows are fully padded
        assert np.all(idx[t, 3] == -1) and np.isinf(costs[t, 3]).all()
    # CSR variant agrees on the same topology
    src, dst = np.nonzero(adj)
    keys = np.argsort(src * n + dst, kind="stable")
    src, dst = src[keys], dst[keys]
    indptr = np.searchsorted(src, np.arange(n + 1))
    live = np.ones((T, len(src)), bool)
    cc, cd = ops.topk_neighbors_csr(
        np.asarray(c_link[:, src, dst], np.float32),
        np.asarray(c_next, np.float32), indptr, dst, live, k=k)
    cc, cd = np.asarray(cc), np.asarray(cd)
    kk = cc.shape[-1]
    np.testing.assert_array_equal(cd, idx[..., :kk])
    np.testing.assert_allclose(cc, costs[..., :kk], rtol=1e-6)
