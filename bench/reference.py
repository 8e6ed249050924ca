"""The plain reference the benchmark holds the timed path to.

Written from the paper (arXiv:2004.08488 §III-§V) in straightforward
numpy and ``jax.numpy``, importing nothing of the program and taking
nothing it made; each model's own reference is its module under
``bench/models/``:

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
  precision, for any model module.
"""
from __future__ import annotations

import json
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
# local SGD over a model found by name
# --------------------------------------------------------------------------
#
# A model is a module ``bench/models/<name>.py`` (``run.load_plugin``) with
# ``dataset(config)``, ``program_model(config)``, ``init(config, seed)``,
# ``loss(config, p, x, y, w, precision)``, ``test_loss(config, p, x_te,
# y_te, precision)``, ``forward_flops(config)`` and, optionally,
# ``REF_DEVICE_BLOCK``: how many devices' weights the reference holds at
# once (all ``n`` where it is absent). With a block b < n the first window
# is walked device-major, b devices at a time, each block padded to its own
# busiest cell: the weights held are at most the initial ones, a float32
# accumulator of eq. (4) and one block's weights before and after a step,
# (2 + 2b) |θ| in float32, plus the step's own temporaries; no array of n
# copies of the weights is ever made.


def gaussian_leaves(leaves: dict, seed: int):
    """Initial weights of ``leaves`` (name -> (shape, fan-in), or (shape,
    None) for zeros) from the job's seed: N(0, 1/fan_in) per gaussian leaf
    with the key folded by the CRC-32 of the leaf's path, zeros for
    biases."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    out = {}
    for name, (shape, fan_in) in leaves.items():
        if fan_in is None:
            out[name] = jnp.zeros(shape, jnp.float32)
            continue
        k = jax.random.fold_in(
            key, zlib.crc32(f"['{name}']".encode()) & 0x7FFFFFFF)
        out[name] = (jax.random.normal(k, shape, jnp.float32)
                     / np.sqrt(fan_in))
    return out


def weighted_xent(logits, y, w):
    """Cross-entropy of one batch, weighted by ``w``: log-probabilities in
    the logits' dtype, the batch mean in float32."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits)
    ll = jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
    return -(ll.astype(jnp.float32) * w).sum() / jnp.maximum(w.sum(), 1.0)


def blocked_xent(logits_fn, x, y, block: int):
    """Mean cross-entropy of a test set, ``block`` rows at a time."""
    import jax
    import jax.numpy as jnp

    xs = x.reshape(-1, block, *x.shape[1:])
    ys = y.reshape(-1, block, *y.shape[1:])

    def blk(_, xy):
        logp = jax.nn.log_softmax(logits_fn(xy[0]))
        ll = jnp.take_along_axis(logp, xy[1][:, None], axis=1)[:, 0]
        return None, -ll.astype(jnp.float32).sum()

    _, s = jax.lax.scan(blk, None, (xs, ys))
    return s.sum() / y.shape[0]


def _as(a, dt):
    """An input on the device: floating arrays (pixels) in ``dt``,
    integer ones (token ids) as they are."""
    import jax.numpy as jnp

    a = np.asarray(a)
    return jnp.asarray(a, dt) if np.issubdtype(a.dtype, np.floating) \
        else jnp.asarray(a)


def first_window(model, config: dict, seed: int, data, rounds, *,
                 precision, dtype="float32", fault=None):
    """Local SGD of every device over ``rounds`` (list of τ + 1 (n,) lists
    of sample-id arrays: the processed cells of the first window and of
    the round after it), eq. (4) over the devices that held data after the
    first τ, the test loss of the aggregate, then round τ from the
    aggregate on every device.

    ``model`` is the configuration's model module, ``data`` its
    ``(x_tr, y_tr, x_te, y_te)``; the step size is ``config["eta"]``. With
    one block of devices (no ``REF_DEVICE_BLOCK``, or one of n or more) all
    n devices step at once on batches padded to ``config["max_points"]``;
    with blocks of b < n devices each block walks the window on its own,
    its batch padded to its busiest cell of the round (a power of two, at
    least 8). Returns (losses (τ + 1, n) float64, test_loss float).
    ``dtype`` is the dtype of the weights, the floating inputs and every
    activation; ``precision`` that of every matmul and convolution, as
    ``jax.lax.Precision`` names it ("default": one bfloat16 pass on the
    TPU). ``fault`` plants one of the faults the comparison must catch:
    "unchanged" (the step returns the weights it got), "half_batch" (the
    second half of every device's batch is left out), "no_broadcast" (the
    devices keep their own models after the aggregation).
    """
    import jax
    import jax.numpy as jnp

    tree_map = jax.tree_util.tree_map
    x_tr, y_tr, x_te, y_te = data
    dt = jnp.dtype(dtype)
    n = len(rounds[0])
    tau = len(rounds) - 1
    block = int(getattr(model, "REF_DEVICE_BLOCK", n))

    def stage(row, P):
        idx = np.zeros((len(row), P), np.int64)
        w = np.zeros((len(row), P), np.float32)
        for i, ix in enumerate(row):
            k = len(ix)
            if fault == "half_batch":
                k_used = -(-k // 2)
            else:
                k_used = k
            idx[i, :k] = ix
            w[i, :k_used] = 1.0
        return _as(x_tr[idx], dt), jnp.asarray(y_tr[idx]), jnp.asarray(w)

    w0 = tree_map(lambda a: a.astype(dt), model.init(config, seed))

    def stack(w, m):
        return tree_map(lambda a: jnp.broadcast_to(a, (m,) + a.shape), w)

    step = _step_program(model, config, precision, fault == "unchanged")
    test = _test_program(model, config, precision)
    # H counts what each device processed, whatever a fault dropped
    H = np.array([[len(ix) for ix in row] for row in rounds[:-1]],
                 np.float64).sum(axis=0)
    tot = H.sum()
    Hj = jnp.asarray(H, jnp.float32)

    def weigh(a, Hb):
        return jnp.einsum("n...,n->...", a.astype(jnp.float32), Hb,
                          precision=precision)

    if block >= n:
        # one batch shape for every job of a cell: the cell's pad size,
        # unless a job holds more
        P = max(int(config["max_points"]),
                max(len(ix) for row in rounds for ix in row))
        P = -(-P // 8) * 8
        W = stack(w0, n)
        losses = []
        for row in rounds[:-1]:
            W, loss = step(W, *stage(row, P))
            losses.append(np.asarray(loss, np.float64))
        wg = tree_map(lambda a: (weigh(a, Hj) / tot).astype(dt), W)
        test_loss = test(wg, _as(x_te, dt), jnp.asarray(y_te))
        if fault != "no_broadcast":
            W = stack(wg, n)
        _, loss = step(W, *stage(rounds[-1], P))
        losses.append(np.asarray(loss, np.float64))
        return np.stack(losses), float(test_loss)

    # devices are independent between aggregations, so the window is walked
    # device-major and only one block's weights are ever held
    losses = np.zeros((tau + 1, n))
    spans = [(lo, min(lo + block, n)) for lo in range(0, n, block)]

    def train(W, t, lo, hi):
        row = rounds[t][lo:hi]
        P = max(8, 1 << (max(len(ix) for ix in row) - 1).bit_length())
        W, loss = step(W, *stage(row, P))
        losses[t, lo:hi] = np.asarray(loss, np.float64)
        return W

    leaves, treedef = jax.tree_util.tree_flatten(w0)
    acc = [jnp.zeros(a.shape, jnp.float32) for a in leaves]

    def add(W, Hb):
        # eq. (4) summed leaf by leaf, so that one leaf at a time is doubled
        for i, a in enumerate(jax.tree_util.tree_leaves(W)):
            acc[i] = acc[i] + weigh(a, Hb)

    for lo, hi in spans:
        W = stack(w0, hi - lo)
        for t in range(tau):
            W = train(W, t, lo, hi)
        add(W, Hj[lo:hi])
        if fault == "no_broadcast":
            train(W, tau, lo, hi)
        del W
    wg = jax.tree_util.tree_unflatten(
        treedef, [(a / tot).astype(dt) for a in acc])
    del acc
    test_loss = test(wg, _as(x_te, dt), jnp.asarray(y_te))
    if fault != "no_broadcast":
        for lo, hi in spans:
            train(stack(wg, hi - lo), tau, lo, hi)
    return losses, float(test_loss)


# compiled programs by (kind, model module, configuration, ...); each
# entry holds its module, so that no other module takes its id
_PROGRAMS: dict = {}


def _program(kind, model, config, rest, make):
    key = (kind, id(model), json.dumps(config, sort_keys=True, default=repr),
           *rest)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = (model, make())
    return _PROGRAMS[key][1]


def _step_program(model, config, precision, unchanged):
    import jax
    import jax.numpy as jnp

    eta = float(config["eta"])

    def make():
        def one(p, x, y, w):
            loss, g = jax.value_and_grad(
                lambda q: model.loss(config, q, x, y, w, precision))(p)
            if unchanged:
                return p, loss
            scale = jnp.minimum(w.sum(), 1.0)
            return jax.tree_util.tree_map(
                lambda a, b: (a - eta * scale * b).astype(a.dtype), p, g), loss

        return jax.jit(jax.vmap(one))

    return _program("step", model, config, (precision, unchanged), make)


def _test_program(model, config, precision):
    import jax

    return _program("test", model, config, (precision,), lambda: jax.jit(
        lambda p, x, y: model.test_loss(config, p, x, y, precision)))
