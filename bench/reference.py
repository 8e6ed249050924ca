"""The plain reference the benchmark holds the timed path to.

Written from the paper (arXiv:2004.08488 §III-§V) and the model
definitions, in straightforward numpy and ``jax.numpy``, importing nothing
of the program and taking nothing it made:

* ``greedy_rule``: Theorem 3 in float64: each (t, i) processes, offloads
  to its cheapest out-neighbour k (cost c_ik(t) + c_k(t+1)) or discards
  (cost f_i(t)), whichever is least; ties go process < offload < discard,
  and the lowest k among equal neighbours;
* ``repair``: the capacity repair of Theorem 6's guidance as a plain loop
  over the plan's non-zero shares: cap link transfers, cut receivers that
  would overflow at t+1 (senders in ascending order), cap local
  processing, each spilled share going back to local processing when
  that is cheaper than discarding and fits, else to discard;
* ``split_counts``: how many of a cell's samples each share of a plan
  routes where (contiguous floor splits of the cell);
* ``first_window``: local SGD (eq. 3) on every device for the first τ
  rounds, the H-weighted aggregation (eq. 4), the test loss of the
  aggregated model, and the first round after it, which every device
  starts from the aggregated model, at a stated dtype and matmul
  precision.
"""
from __future__ import annotations

import zlib

import numpy as np

F32_EPS = 2.0 ** -23


# --------------------------------------------------------------------------
# movement plane
# --------------------------------------------------------------------------


def greedy_rule(c_node, c_link, f_err, adj, dtype=np.float64):
    """Theorem 3 decisions: (T, n) int64, -1 discard, else the device
    that processes the cell (i itself, or its offload target)."""
    c_node = np.asarray(c_node, dtype)
    c_link = np.asarray(c_link, dtype)
    f_err = np.asarray(f_err, dtype)
    T, n = c_node.shape
    dec = np.empty((T, n), np.int64)
    rows = np.arange(n)
    blocked = ~np.asarray(adj, bool) | np.eye(n, dtype=bool)
    for t in range(T):
        if t + 1 < T:
            eff = c_link[t] + c_node[t + 1][None, :]
            eff = np.where(blocked, np.inf, eff).astype(dtype)
            k = eff.argmin(axis=1)
            off = eff[rows, k]
        else:
            k = rows
            off = np.full(n, np.inf, dtype)
        proc, disc = c_node[t], f_err[t]
        dec[t] = np.where((proc <= off) & (proc <= disc), rows,
                          np.where(off <= disc, k, -1))
    return dec


def decision_cost(c_node, c_link, f_err, dec):
    """float64 cost of each (t, i) decision."""
    T, n = dec.shape
    t, i = np.meshgrid(np.arange(T), np.arange(n), indexing="ij")
    c_next = np.concatenate([c_node[1:], c_node[-1:]])
    j = np.maximum(dec, 0)
    off = c_link[t, i, j] + c_next[t, j]
    return np.where(dec < 0, f_err, np.where(dec == i, c_node, off))


def wrong_decisions(c_node, c_link, f_err, dec, dec_ref) -> int:
    """Decisions of ``dec`` that differ from the float64 rule by more than
    float32 resolution: a candidate cost is a sum of at most two
    float32-rounded costs, so 4 ulps of the larger magnitude."""
    diff = dec != dec_ref
    if not diff.any():
        return 0
    cp = decision_cost(c_node, c_link, f_err, dec)[diff]
    cr = decision_cost(c_node, c_link, f_err, dec_ref)[diff]
    tol = 4 * F32_EPS * np.maximum(np.abs(cp), np.abs(cr))
    return int((np.abs(cp - cr) > tol).sum())


def repair(s, r, c_node, f_err, cap, adj, D, dtype=np.float64):
    """Capacity repair of a dense plan (s (T, n, n), r (T, n)) with one
    capacity ``cap`` on every node and link; returns repaired copies.
    ``dtype`` is the precision of every quantity."""
    s = np.array(s, dtype)
    r = np.array(r, dtype)
    c_node, f_err = np.asarray(c_node, dtype), np.asarray(f_err, dtype)
    D, cap = np.asarray(D, dtype), dtype(cap)
    adj = np.asarray(adj, bool)
    T, n = r.shape

    def revert(t, i, spill, Dt, arrivals):
        cap_left = cap - (s[t, i, i] * Dt[i] + arrivals[i])
        if c_node[t, i] <= f_err[t, i] and cap_left >= spill * Dt[i]:
            s[t, i, i] += spill
        else:
            r[t, i] += spill

    for t in range(T):
        Dt = D[t]
        if t > 0:
            vol = s[t - 1] * D[t - 1][:, None]
            arrivals = vol.sum(0) - np.diag(s[t - 1]) * D[t - 1]
        else:
            arrivals = np.zeros(n, dtype)
        # link capacity, source-major
        ii, jj = np.nonzero(s[t])
        for i, j in zip(ii, jj):
            if i == j or not adj[i, j]:
                continue
            if s[t, i, j] * Dt[i] > cap:
                spill = s[t, i, j] - cap / max(Dt[i], 1e-12)
                s[t, i, j] -= spill
                revert(t, i, spill, Dt, arrivals)
        # receivers' capacity at t+1, where arrivals are processed
        if t + 1 < T:
            vol = s[t] * Dt[:, None]
            inc = vol.sum(0) - np.diag(s[t]) * Dt
            over = inc + np.diag(s[t + 1]) * D[t + 1] - cap
            for j in np.nonzero(over > 1e-9)[0]:
                excess = over[j]
                for i in np.nonzero(vol[:, j] > 0)[0]:
                    if i == j:
                        continue
                    if excess <= 1e-12:
                        break
                    cut = min(vol[i, j], excess)
                    spill = cut / max(Dt[i], 1e-12)
                    s[t, i, j] -= spill
                    excess -= cut
                    revert(t, i, spill, Dt, arrivals)
        # own capacity at t
        over = np.diag(s[t]) * Dt + arrivals - cap
        for i in np.nonzero(over > 1e-9)[0]:
            cut = min(s[t, i, i] * Dt[i], over[i])
            spill = cut / max(Dt[i], 1e-12)
            s[t, i, i] -= spill
            r[t, i] += spill
    return s, r


def decisions_to_plan(dec):
    """Bang-bang plan (s, r) of (T, n) decisions."""
    T, n = dec.shape
    s = np.zeros((T, n, n))
    r = np.zeros((T, n))
    t, i = np.nonzero(dec >= 0)
    s[t, i, dec[t, i]] = 1.0
    r[dec < 0] = 1.0
    return s, r


def split_counts(s, r, D):
    """(T, n, n) samples of cell (t, i) routed to destination j: shares
    clipped at 0 and normalised with the discard share last, the cell cut
    into contiguous floor splits. j == i is processed at t, j != i at
    t+1."""
    T, n = r.shape
    out = np.zeros((T, n, n), np.int64)
    for t in range(T):
        fr = np.clip(np.concatenate([s[t], r[t][:, None]], axis=1), 0, None)
        fr = fr / np.maximum(fr.sum(axis=1, keepdims=True), 1e-12)
        cuts = np.floor(np.cumsum(fr, axis=1) * D[t][:, None] + 1e-9)
        ends = cuts[:, :-1].astype(np.int64)
        starts = np.concatenate([np.zeros((n, 1), np.int64), ends[:, :-1]],
                                axis=1)
        out[t] = np.maximum(ends - starts, 0)
    return out


# --------------------------------------------------------------------------
# models and local SGD
# --------------------------------------------------------------------------

# name -> (shape, fan-in) of a gaussian leaf, or (shape, None) for zeros
MODEL_LEAVES = {
    "mlp": {"w1": ((784, 200), 784), "b1": ((200,), None),
            "w2": ((200, 10), 200), "b2": ((10,), None)},
    "cnn": {"c1": ((5, 5, 1, 16), 25), "cb1": ((16,), None),
            "c2": ((5, 5, 16, 32), 400), "cb2": ((32,), None),
            "w1": ((1568, 128), 1568), "b1": ((128,), None),
            "w2": ((128, 10), 128), "b2": ((10,), None)},
}


def init_model(model: str, seed: int):
    """Initial weights from the job's seed: N(0, 1/fan_in) per gaussian
    leaf with the key folded by the CRC-32 of the leaf's path, zeros for
    biases."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    out = {}
    for name, (shape, fan_in) in MODEL_LEAVES[model].items():
        if fan_in is None:
            out[name] = jnp.zeros(shape, jnp.float32)
            continue
        k = jax.random.fold_in(
            key, zlib.crc32(f"['{name}']".encode()) & 0x7FFFFFFF)
        out[name] = (jax.random.normal(k, shape, jnp.float32)
                     / np.sqrt(fan_in))
    return out


def _apply(model, p, x, precision):
    import jax
    import jax.numpy as jnp

    if model == "mlp":
        h = jnp.dot(x.reshape(x.shape[0], -1), p["w1"],
                    precision=precision) + p["b1"]
        h = jnp.maximum(h, 0)
        return jnp.dot(h, p["w2"], precision=precision) + p["b2"]

    def conv(h, w, b):
        y = jax.lax.conv_general_dilated(
            h, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=precision)
        return jnp.maximum(y + b, 0)

    def pool(h):
        B, H, W, C = h.shape
        return h.reshape(B, H // 2, 2, W // 2, 2, C).max(axis=(2, 4))

    h = pool(conv(x[..., None], p["c1"], p["cb1"]))
    h = pool(conv(h, p["c2"], p["cb2"]))
    h = h.reshape(h.shape[0], -1)
    h = jnp.maximum(jnp.dot(h, p["w1"], precision=precision) + p["b1"], 0)
    return jnp.dot(h, p["w2"], precision=precision) + p["b2"]


def _loss(model, p, x, y, w, precision):
    import jax
    import jax.numpy as jnp

    # log-probabilities in the weights' dtype; the batch mean in float32
    logp = jax.nn.log_softmax(_apply(model, p, x, precision))
    ll = jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
    return -(ll.astype(jnp.float32) * w).sum() / jnp.maximum(w.sum(), 1.0)


def first_window(model: str, seed: int, eta: float, x_tr, y_tr, x_te, y_te,
                 rounds, *, precision, pad=8, dtype="float32", fault=None):
    """Local SGD of every device over ``rounds`` (list of τ + 1 (n,) lists
    of sample-id arrays: the processed cells of the first window and of
    the round after it), eq. (4) over the devices that held data after the
    first τ, the test loss of the aggregate, then round τ from the
    aggregate on every device.

    Returns (losses (τ + 1, n) float64, test_loss float). ``dtype`` is the
    dtype of the weights, the pixels and every activation; ``precision``
    that of every matmul and convolution, as ``jax.lax.Precision`` names
    it ("default": one bfloat16 pass on the TPU). ``fault``
    plants one of the faults the comparison must catch: "unchanged" (the
    step returns the weights it got), "half_batch" (the second half of
    every device's batch is left out), "no_broadcast" (the devices keep
    their own models after the aggregation).
    """
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    n = len(rounds[0])
    # one batch shape for every job of a cell: the cell's pad size, unless
    # a job holds more
    P = max(pad, max(len(ix) for row in rounds for ix in row))
    P = -(-P // 8) * 8

    def stage(row):
        idx = np.zeros((n, P), np.int64)
        w = np.zeros((n, P), np.float32)
        for i, ix in enumerate(row):
            k = len(ix)
            if fault == "half_batch":
                k_used = -(-k // 2)
            else:
                k_used = k
            idx[i, :k] = ix
            w[i, :k_used] = 1.0
        return (jnp.asarray(x_tr[idx], dt), jnp.asarray(y_tr[idx]),
                jnp.asarray(w))

    w0 = {k: v.astype(dt) for k, v in init_model(model, seed).items()}

    def stack(w):
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (n,) + a.shape), w)

    W = stack(w0)
    step = _step_program(model, float(eta), precision, fault == "unchanged")
    H = np.zeros(n)
    losses = []
    for row in rounds[:-1]:
        W, loss = step(W, *stage(row))
        losses.append(np.asarray(loss, np.float64))
        # H counts what each device processed, whatever a fault dropped
        H += np.array([len(ix) for ix in row], np.float64)
    tot = H.sum()
    Hj = jnp.asarray(H, jnp.float32)
    wg = jax.tree_util.tree_map(
        lambda a: (jnp.einsum("n...,n->...", a.astype(jnp.float32), Hj,
                              precision=precision) / tot).astype(dt), W)
    test_loss = _eval_program(model, precision)(
        wg, jnp.asarray(x_te, dt), jnp.asarray(y_te))
    if fault != "no_broadcast":
        W = stack(wg)
    _, loss = step(W, *stage(rounds[-1]))
    losses.append(np.asarray(loss, np.float64))
    return np.stack(losses), float(test_loss)


_PROGRAMS: dict = {}


def _step_program(model, eta, precision, unchanged):
    import jax
    import jax.numpy as jnp

    key = ("step", model, eta, precision, unchanged)
    if key not in _PROGRAMS:
        def one(p, x, y, w):
            loss, g = jax.value_and_grad(
                lambda q: _loss(model, q, x, y, w, precision))(p)
            if unchanged:
                return p, loss
            scale = jnp.minimum(w.sum(), 1.0)
            return jax.tree_util.tree_map(
                lambda a, b: (a - eta * scale * b).astype(a.dtype), p, g), loss

        _PROGRAMS[key] = jax.jit(jax.vmap(one))
    return _PROGRAMS[key]


def _eval_program(model, precision):
    import jax
    import jax.numpy as jnp

    key = ("eval", model, precision)
    if key not in _PROGRAMS:
        def ev(p, x, y):
            # blocks of 1,000 test images keep the activations small
            xs = x.reshape(-1, 1000, *x.shape[1:])
            ys = y.reshape(-1, 1000)

            def blk(_, xy):
                logp = jax.nn.log_softmax(_apply(model, p, xy[0], precision))
                ll = jnp.take_along_axis(logp, xy[1][:, None], axis=1)[:, 0]
                return None, -ll.astype(jnp.float32).sum()

            _, s = jax.lax.scan(blk, None, (xs, ys))
            return s.sum() / y.shape[0]

        _PROGRAMS[key] = jax.jit(ev)
    return _PROGRAMS[key]
