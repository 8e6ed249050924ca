"""Scan- and shard-compiled federated training engine.

The hot path of ``run_network_aware`` used to dispatch T separate jitted
steps, re-padding and re-uploading the batch tensor every round.  Here
the whole horizon is one device-resident program:

* the padded sample stream is staged once as ``(T, n, P)`` index /
  label / weight arrays (indices gathered on host, pixels gathered on
  device — either up front when the ``(T, n, P, ...)`` tensor fits
  ``PRESTAGE_LIMIT_BYTES``, or per-round inside the scan body);
* the vmapped local-SGD step (eq. 3), the every-τ H-weighted
  aggregation (eq. 4), synchronization, churn masking and
  H-accumulation are folded into a single ``jax.lax.scan`` over rounds.
  Nothing is donated: no output has the shape of the parameter stack,
  so XLA could reuse none of its buffers.

``run_rounds_batched`` makes the SWEEP axis itself a compiled
dimension: S scenarios — padded up to a shared shape bucket
(``data/pipeline.stage_scenario_batch``) — train in ONE program whose
round axis is scanned as (T/τ, τ) aggregation windows with a
double-buffered aggregation carry (window w's epilogue issues the
H-weighted sums, window w+1's prologue realizes divide + sync, so the
cross-shard ``psum`` on a mesh can overlap the next window's gather
and first local steps). Programs are cached per (model, η, staging
mode, mesh) and jit retraces once per shape bucket, so a whole sweep
compiles #buckets programs (``batched_compile_count``).

``run_rounds_sharded`` is the S=1 slice of the batched path with the
fog-device axis partitioned across a 1-D "data" mesh via ``shard_map``
(``distributed/sharding.py``, ``launch/mesh.make_data_mesh``);
the every-τ H-weighted aggregation is a cross-shard ``psum``
reduction. Test evaluation is streamed OFF the hot path by an
:class:`AsyncEvaluator` — the scan emits per-window global-parameter
snapshots and one stacked vmapped eval dispatch drains a whole
bucket's queue after training, so no per-τ blocking ``eval_fn`` sits
inside a sweep loop.

``run_rounds_legacy`` preserves the original per-round Python loop —
it is the numerical oracle for the equivalence tests and the baseline
for the ``engine_throughput`` benchmark.
"""
from __future__ import annotations

import collections
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sanitize
from repro.data import pipeline as pl
from repro.models import mnist as mm
from repro.models.module import init_params

# Above this size the (T, n, P, ...) pixel tensor is not materialized;
# pixels are gathered from the device-resident training set inside the
# scan body instead (same program, lower peak memory at fog scale).
PRESTAGE_LIMIT_BYTES = 256 * 1024 ** 2

# dataset tensors pinned on device across engine invocations (sweeps call
# the engine many times with the same train/test arrays); values keep the
# host array alive so the id() key cannot be recycled, and a sampled
# checksum catches in-place mutation (normalization/augmentation) between
# calls — sparse point edits can still slip through, so treat arrays
# passed to the engine as immutable.  LRU: only the least-recently-used
# entry is evicted at capacity, so the datasets a sweep keeps touching
# stay pinned instead of being flushed wholesale mid-sweep.
_DEVICE_CACHE_CAP = 16
_DEVICE_CACHE: collections.OrderedDict = collections.OrderedDict()


def _to_device_cached(arr: np.ndarray):
    arr = np.asarray(arr)
    flat = arr.reshape(-1)
    sample = flat[::max(1, flat.size // 4096)]
    key = (id(arr), arr.shape, str(arr.dtype),
           float(np.asarray(sample, np.float64).sum()))
    hit = _DEVICE_CACHE.get(key)
    if hit is None:
        while len(_DEVICE_CACHE) >= _DEVICE_CACHE_CAP:
            _DEVICE_CACHE.popitem(last=False)     # oldest entry only
        hit = _DEVICE_CACHE[key] = (arr, jnp.asarray(arr))
    else:
        _DEVICE_CACHE.move_to_end(key)
    return hit[1]


def make_model(name: str, rng):
    specs_fn, apply_fn = mm.MODELS[name]
    params = init_params(specs_fn(), rng, jnp.float32)
    return params, apply_fn


def resolve_engine(engine: str) -> str:
    """The single "auto" dispatch rule shared by every caller (CLI,
    examples, Scenario sweeps): sharded whenever a data mesh of more
    than one device is available, scan otherwise."""
    if engine == "auto":
        return "sharded" if jax.device_count() > 1 else "scan"
    return engine


def _stack(params, n):
    return jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p, (n, *p.shape)).copy(), params)


def _device_step_fn(apply_fn, eta):
    def one(params, xb, yb, w, active):
        def lf(p):
            return mm.ce_loss(apply_fn(p, xb), yb, w)

        loss, g = jax.value_and_grad(lf)(params)
        scale = active * jnp.minimum(w.sum(), 1.0)   # no data -> no update
        new = jax.tree_util.tree_map(lambda p, gg: p - eta * scale * gg,
                                     params, g)
        return new, loss

    return one


def _row_loss_fn(apply_fn):
    """UNNORMALIZED weighted CE of one ragged chunk row — the summand
    of ``mm.ce_loss``'s numerator. The ragged engine sums these per
    device through the segment reduce and divides by the staged sample
    count afterwards (the counts equal the dense path's ``w.sum()``
    exactly: 0/1 weights sum to exact integers), so the per-device loss
    and gradient match the dense step up to summation order."""

    def lf(p, xb, yb, w):
        logp = jax.nn.log_softmax(apply_fn(p, xb).astype(jnp.float32))
        ll = jnp.take_along_axis(logp, yb[:, None], axis=1)[:, 0]
        return -(ll * w).sum()

    return lf


def make_device_step(apply_fn, eta):
    return jax.jit(jax.vmap(_device_step_fn(apply_fn, eta)))


def aggregate(W, H: jnp.ndarray, contributing: jnp.ndarray, prev_global):
    """Eq. (4): w(k) = Σ H_i w_i / Σ H_i over contributing devices."""
    Hc = H * contributing
    tot = Hc.sum()

    def agg(a):
        return jnp.where(tot > 0,
                         jnp.einsum("n...,n->...", a, Hc) / jnp.maximum(tot, 1e-9),
                         0.0)

    w_new = jax.tree_util.tree_map(agg, W)
    if prev_global is not None:
        w_new = jax.tree_util.tree_map(
            lambda new, old: jnp.where(tot > 0, new, old), w_new, prev_global)
    return w_new


def aggregate_edges(W, H: jnp.ndarray, device_ids, prev_global, *,
                    use_pallas=None):
    """Eq. (4) with the contributing set as an explicit device LIST
    (edge-list form) instead of a dense (n,) mask: w(k) = Σ H_i w_i /
    Σ H_i over ``device_ids``, the H-weighted sums computed through the
    segment-reduce kernel dispatch (``kernels.ops.segment_sum`` — one
    segment per parameter, elements are the listed contributors). The
    sparse twin of :func:`aggregate`: equal up to summation order for
    the mask with exactly those ids set."""
    from repro.kernels import ops
    ids = jnp.asarray(device_ids, jnp.int32)
    k = ids.shape[0]
    Hc = H[ids]
    tot = Hc.sum()

    def agg(a):
        P = int(np.prod(a.shape[1:], dtype=np.int64)) or 1
        flat = a[ids].reshape(k, P) * Hc[:, None]        # (k, P)
        seg = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32)[None],
                               (k, P)).reshape(-1)
        s = ops.segment_sum(flat.reshape(-1), seg, num_segments=P,
                            use_pallas=use_pallas)
        return jnp.where(tot > 0, s / jnp.maximum(tot, 1e-9),
                         0.0).reshape(a.shape[1:]).astype(a.dtype)

    w_new = jax.tree_util.tree_map(agg, W)
    if prev_global is not None:
        w_new = jax.tree_util.tree_map(
            lambda new, old: jnp.where(tot > 0, new, old), w_new,
            prev_global)
    return w_new


def aggregate_tier(W, H: jnp.ndarray, group_ids, num_groups: int, *,
                   use_pallas=None):
    """Eq. (4) applied PER GROUP of one tier: ``W`` is a (m, ...) stack
    (devices at tier 1, child groups above), ``H`` the (m,) cumulative
    weights, ``group_ids`` the (m,) member→group map. Returns the
    ``(num_groups, ...)`` stack of group models plus the per-group
    weight totals ``H_g = segment_sum(H)``, so tiers compose: feeding
    the outputs straight back in telescopes to the flat eq. (4) over
    the union. One segment-reduce per leaf — segments are (group,
    parameter) pairs — through the same ``kernels.ops.segment_sum``
    dispatch as :func:`aggregate_edges`, with identical divide/where
    arithmetic: a group's row is bitwise what ``aggregate_edges`` over
    its ascending member list produces. Empty groups (H_g == 0) come
    back as zeros — callers mask on ``H_g > 0``."""
    from repro.kernels import ops
    gi = jnp.asarray(group_ids, jnp.int32)
    m = gi.shape[0]
    Hg = ops.segment_sum(H, gi, num_segments=num_groups,
                         use_pallas=use_pallas)

    def agg(a):
        P = int(np.prod(a.shape[1:], dtype=np.int64)) or 1
        flat = a.reshape(m, P) * H[:, None]              # (m, P)
        seg = (gi[:, None] * np.int32(P)
               + jnp.arange(P, dtype=jnp.int32)[None]).reshape(-1)
        s = ops.segment_sum(flat.reshape(-1), seg,
                            num_segments=num_groups * P,
                            use_pallas=use_pallas).reshape(num_groups, P)
        out = jnp.where(Hg[:, None] > 0,
                        s / jnp.maximum(Hg, 1e-9)[:, None], 0.0)
        return out.reshape((num_groups,) + a.shape[1:]).astype(a.dtype)

    return jax.tree_util.tree_map(agg, W), Hg


def _sync(W, w_global, active):
    def s(stack, g):
        mask = active.reshape((-1,) + (1,) * g.ndim)
        return jnp.where(mask, g[None], stack)

    return jax.tree_util.tree_map(s, W, w_global)


def _finite_mask(W, batch_axes: int):
    """1.0 where every parameter leaf of a device is finite — the
    guarded-aggregation mask. ``batch_axes`` leading axes index the
    device ((n, ...) on the scan path, (S, n, ...) on the batched
    path). All-finite inputs produce an all-ones mask, and masking
    with an all-ones mask is bitwise the identity, so the guard is an
    exact no-op on clean uploads."""
    ok = None
    for p in jax.tree_util.tree_leaves(W):
        sh = p.shape[:batch_axes]
        fin = jnp.all(jnp.isfinite(p.reshape(sh + (-1,))), axis=-1)
        ok = fin if ok is None else ok & fin
    return ok.astype(jnp.float32)


def _guarded_uploads(W, contributing, upl, cor, guard: bool,
                     batch_axes: int):
    """What the aggregator actually receives: device params scaled by
    the per-link corruption multiplier, missing uploads masked out of
    the contributing set, and — when ``guard`` — non-finite updates
    finite-masked (with the H-weight total renormalizing over the
    surviving set simply because the masked devices contribute zero H).
    With identity fault views (upl == cor == 1) every step multiplies
    by 1.0 or selects through an all-true mask, so the result is
    bitwise-identical to the unguarded inputs."""
    tree_map = jax.tree_util.tree_map
    contributing = contributing * upl
    Wu = tree_map(
        # foglint: disable=nan-unsafe-masking -- intentional fault injection, not a guard: cor is a finite corruption multiplier on the upload; the protective select below uses jnp.where
        lambda p: p * cor.reshape(cor.shape + (1,) * (p.ndim - batch_axes)),
        W)
    if guard:
        ok = _finite_mask(Wu, batch_axes)
        contributing = contributing * ok
        # zero (not just de-weight) masked devices: NaN * 0 is NaN, so
        # a poisoned leaf must never enter the reduction at all
        Wu = tree_map(
            lambda p: jnp.where(
                ok.reshape(ok.shape + (1,) * (p.ndim - batch_axes)) > 0,
                p, 0.0), Wu)
    return Wu, contributing


# ---------------------------------------------------------------------------
# scan-compiled path
# ---------------------------------------------------------------------------


def _make_scan_body(apply_fn, vstep, prestage: bool, faults: bool,
                    guard: bool, quorum: float, x_tr, x_te, y_te,
                    hier=None):
    """The per-round scan body, shared by the monolithic program and
    the window-chunked checkpoint driver (same closure -> same jaxpr ->
    the chunked dispatches reproduce the monolithic scan bit for bit).
    With ``faults`` the xs gain (upload_ok, corrupt) rows and the
    aggregation runs guarded + quorum-gated; without, the trace is
    exactly the historical clean program.

    ``hier`` — optional :class:`_HierSpec`: the xs gain a trailing
    per-round ``lvl`` row (highest aggregating tier, 0 = none) and the
    aggregation branch composes eq. (4) up the tier tree instead of
    straight to the server (see :func:`run_rounds_hierarchical`). With
    ``hier=None`` this function is untouched — the flat trace is the
    historical program, bit for bit."""
    tree_map = jax.tree_util.tree_map

    def body(carry, xs):
        W, wg, H, waiting = carry
        if hier is not None:
            xs, lvl = xs[:-1], xs[-1]
        if faults:
            xb, idx, yb, w, cnt, a, agg, upl, cor = xs
        else:
            xb, idx, yb, w, cnt, a, agg = xs
        if not prestage:
            xb = jnp.take(x_tr, idx, axis=0)
        active = a * (1.0 - waiting)
        W, losses = vstep(W, xb, yb, w, active)
        H = H + cnt * active

        def do_agg(ops):
            W, wg, H, waiting = ops
            if faults:
                Wu, contrib = _guarded_uploads(W, active, upl, cor,
                                               guard, 1)
                surv = contrib.sum()
                qok = surv >= quorum * active.sum()
                wg2 = aggregate(Wu, H, contrib, wg)
                # quorum failed: the whole aggregation event is skipped
                # — previous global carries forward, no sync, H keeps
                # accumulating into the next window
                wg2 = tree_map(lambda nw, old: jnp.where(qok, nw, old),
                               wg2, wg)
                W2 = _sync(W, wg2, (a > 0.5) & qok)
                H2 = jnp.where(qok, jnp.zeros_like(H), H)
                waiting2 = jnp.where(qok, 1.0 - a, waiting)
            else:
                wg2 = aggregate(W, H, active, wg)
                W2 = _sync(W, wg2, a > 0.5)
                H2 = jnp.zeros_like(H)
                waiting2 = 1.0 - a
            logits = apply_fn(wg2, x_te)
            tl = mm.ce_loss(logits, y_te)
            ta = mm.accuracy(logits, y_te)
            out = (W2, wg2, H2, waiting2, tl, ta, H)
            if faults:
                out += (surv, qok.astype(jnp.float32))
            return out

        def skip(ops):
            W, wg, H, waiting = ops
            z = jnp.float32(0.0)
            out = (W, wg, H, waiting, z, z, H)
            if faults:
                out += (z, jnp.float32(1.0))
            return out

        if hier is not None:
            L = len(hier.num_groups)
            anc = [jnp.asarray(a, jnp.int32) for a in hier.anc]
            is_top = lvl >= L

            def hier_do_agg(ops):
                W, wg, H, waiting = ops
                if faults:
                    Wu, contrib = _guarded_uploads(W, active, upl, cor,
                                                   guard, 1)
                    surv = contrib.sum()
                    qok = surv >= quorum * active.sum()
                else:
                    Wu, contrib = W, active
                    qok = None
                # compose eq. (4) up the tree: tier l aggregates tier
                # l-1's stack under CUMULATIVE H weights, so feeding
                # each tier's (models, H_g) into the next telescopes to
                # the flat eq. (4) over all contributing devices — the
                # top row IS the global model
                Wl, Hl = Wu, H * contrib
                tiers = []
                for gids, ng in zip(hier.group_ids, hier.num_groups):
                    Wl, Hl = aggregate_tier(Wl, Hl, gids, ng)
                    tiers.append((Wl, Hl))
                Wtop, Htop = tiers[-1]
                ok_top = is_top & (Htop[0] > 0)
                if qok is not None:
                    ok_top = ok_top & qok
                wg2 = tree_map(
                    lambda nw, old: jnp.where(ok_top, nw[0], old),
                    Wtop, wg)

                # every device syncs from its ancestor group at the
                # round's highest aggregating tier; empty groups
                # (H_g == 0) leave their members' params untouched
                def pick(lv):
                    Wg, Hg = tiers[lv]
                    return (tree_map(lambda g: g[anc[lv]], Wg),
                            Hg[anc[lv]])

                target, Hsel = jax.lax.switch(
                    jnp.maximum(lvl - 1, 0),
                    [lambda lv=lv: pick(lv) for lv in range(L)])
                sync_ok = (a > 0.5) & (Hsel > 0)
                if qok is not None:
                    sync_ok = sync_ok & qok
                W2 = tree_map(
                    lambda p, tg: jnp.where(
                        sync_ok.reshape(sync_ok.shape
                                        + (1,) * (p.ndim - 1)), tg, p),
                    W, target)
                # H accumulates across sub-tier windows and resets only
                # once the TOP tier has consumed it (that is what makes
                # the tier composition telescope); quorum failure skips
                # the whole event, flat-plane style
                if faults:
                    H2 = jnp.where(is_top & qok, jnp.zeros_like(H), H)
                    waiting2 = jnp.where(qok, 1.0 - a, waiting)
                else:
                    H2 = jnp.where(is_top, jnp.zeros_like(H), H)
                    waiting2 = 1.0 - a

                def ev(_):
                    logits = apply_fn(wg2, x_te)
                    return mm.ce_loss(logits, y_te), mm.accuracy(logits,
                                                                 y_te)

                tl, ta = jax.lax.cond(
                    is_top, ev,
                    lambda _: (jnp.float32(0.0), jnp.float32(0.0)), None)
                out = (W2, wg2, H2, waiting2, tl, ta, H)
                if faults:
                    out += (surv, qok.astype(jnp.float32))
                return out

            do_agg = hier_do_agg

        res = jax.lax.cond(agg, do_agg, skip, (W, wg, H, waiting))
        W, wg, H, waiting = res[:4]
        return (W, wg, H, waiting), (losses,) + res[4:]

    return body


@functools.lru_cache(maxsize=16)
def _scan_program(apply_fn, eta: float, prestage: bool,
                  faults: bool = False, guard: bool = False,
                  quorum: float = 0.0):
    """One jitted program per (model, η, staging mode, fault config);
    the aggregation schedule arrives as the traced ``is_agg`` round
    mask, so changing τ does not recompile. With ``faults=False`` the
    trace (and therefore the bits) is the historical clean program."""

    vstep = jax.vmap(_device_step_fn(apply_fn, eta))

    def train(W0, wg0, x_tr, xb_all, idx_all, yb_all, w_all, counts,
              act, is_agg, x_te, y_te, *fault_ops):
        n = counts.shape[1]
        body = _make_scan_body(apply_fn, vstep, prestage, faults, guard,
                               quorum, x_tr, x_te, y_te)
        carry0 = (W0, wg0, jnp.zeros(n, jnp.float32), jnp.zeros(n, jnp.float32))
        xs = (xb_all, idx_all, yb_all, w_all, counts, act, is_agg)
        xs = xs + tuple(fault_ops)
        (_, wg, _, _), ys = jax.lax.scan(body, carry0, xs)
        return (wg,) + ys

    return jax.jit(train)


@functools.lru_cache(maxsize=16)
def _scan_chunk_program(apply_fn, eta: float, prestage: bool,
                        faults: bool = False, guard: bool = False,
                        quorum: float = 0.0):
    """Window-chunked slice of ``_scan_program``: the SAME scan body
    with the carry explicit in/out, so the checkpoint driver can
    dispatch ``checkpoint_every`` windows at a time and snapshot the
    carry at each boundary. Iterating the identical body over a sliced
    round axis reproduces the monolithic scan bit for bit on CPU."""

    vstep = jax.vmap(_device_step_fn(apply_fn, eta))

    def train(carry, x_tr, xb_all, idx_all, yb_all, w_all, counts,
              act, is_agg, x_te, y_te, *fault_ops):
        body = _make_scan_body(apply_fn, vstep, prestage, faults, guard,
                               quorum, x_tr, x_te, y_te)
        xs = (xb_all, idx_all, yb_all, w_all, counts, act, is_agg)
        xs = xs + tuple(fault_ops)
        return jax.lax.scan(body, carry, xs)

    return jax.jit(train)


def _stage_fault_ops(faults, T: int, n: int, tau: int):
    """Validate a FaultSchedule against the run dims and return the
    device-staged (upload_ok, corrupt) operand pair."""
    if (faults.T, faults.n) != (T, n):
        raise ValueError(f"fault schedule is (T={faults.T}, n={faults.n})"
                         f" but the run is (T={T}, n={n})")
    if faults.tau != tau:
        raise ValueError(f"fault schedule has tau={faults.tau} but the "
                         f"run aggregates every tau={tau}")
    upl, cor = faults.engine_arrays()
    return jnp.asarray(upl), jnp.asarray(cor)


def run_rounds_scan(apply_fn, params, x_tr, y_tr, x_te, y_te, processed,
                    act_all, tau: int, eta: float, max_pts: int, *,
                    faults=None, guard: bool = True, quorum: float = 0.0,
                    checkpoint_path: str | None = None,
                    checkpoint_every: int = 1, resume: str | None = None,
                    stop_after: int | None = None) -> dict:
    """Train all T rounds in one compiled scan; returns history pieces.

    ``faults`` — optional :class:`repro.core.faults.FaultSchedule`:
    crash outages are ANDed into the staged activity and the
    (upload_ok, corrupt) views ride the scan as extra operands, with
    the aggregation guarded (``guard`` finite-masking + H-weight
    renormalization over survivors) and quorum-gated (``quorum`` —
    windows whose surviving-upload fraction falls below it skip the
    aggregation and carry the previous global forward). ``faults=None``
    runs the historical clean program, bitwise-identical to before the
    fault plane existed.

    ``checkpoint_path`` — snapshot (params stack, global, H, waiting,
    history, round index) every ``checkpoint_every`` aggregation
    windows via ``repro.checkpoint.checkpoint``; ``resume`` continues
    a snapshot mid-horizon, bitwise-equal on CPU to an uninterrupted
    run. ``stop_after`` (rounds; checkpointed runs only) simulates an
    interruption at the next window boundary — benches/tests use it to
    produce a mid-horizon checkpoint to resume from."""
    if isinstance(processed, pl.FlatStreams):
        T, n = processed.T, processed.n
    else:
        T, n = len(processed), len(processed[0])
    idx, yb, wts, counts = pl.stage_rounds(processed, y_tr, max_pts)
    is_agg = (np.arange(T) + 1) % tau == 0

    use_faults = faults is not None
    act_arr = np.asarray(act_all)
    fault_ops = ()
    if use_faults:
        act_arr = np.asarray(act_all, bool) & faults.activity_mask()
        fault_ops = _stage_fault_ops(faults, T, n, tau)
    guard_f = bool(guard) if use_faults else False
    quorum_f = float(quorum) if use_faults else 0.0

    x_dev = _to_device_cached(x_tr)
    idx_dev = jnp.asarray(idx)
    item_bytes = int(np.prod(x_tr.shape[1:], dtype=np.int64)) * 4
    prestage = T * n * max_pts * item_bytes <= PRESTAGE_LIMIT_BYTES
    if prestage:
        xb_all, idx_arg = jnp.take(x_dev, idx_dev, axis=0), None
    else:
        xb_all, idx_arg = None, idx_dev

    args = (x_dev, xb_all, idx_arg, jnp.asarray(yb), jnp.asarray(wts),
            jnp.asarray(counts), jnp.asarray(act_arr, jnp.float32),
            jnp.asarray(is_agg), _to_device_cached(x_te),
            _to_device_cached(y_te))

    if checkpoint_path is not None or resume is not None:
        return _run_scan_checkpointed(
            apply_fn, params, n, T, tau, eta, prestage, args, fault_ops,
            use_faults, guard_f, quorum_f, checkpoint_path,
            checkpoint_every, resume, stop_after)

    fn = _scan_program(apply_fn, float(eta), prestage, use_faults,
                       guard_f, quorum_f)
    # sanitize hook: under run_network_aware(sanitize=True) the guard
    # disallows implicit transfers across the whole-horizon dispatch
    # (staging above and history readback below are explicit, by design)
    with sanitize.hot_loop_guard():
        res = fn(_stack(params, n), params, *args, *fault_ops)
        losses, tl, ta, H_at = res[1:5]
        jax.block_until_ready(losses)
    agg_rounds = np.nonzero(is_agg)[0]
    tl, ta, H_at = np.asarray(tl), np.asarray(ta), np.asarray(H_at)
    out = {"device_loss": list(np.asarray(losses)),
           "test_loss": [float(v) for v in tl[agg_rounds]],
           "test_acc": [float(v) for v in ta[agg_rounds]],
           "agg_round": [int(t) for t in agg_rounds],
           "H_agg": list(H_at[agg_rounds])}
    if use_faults:
        surv, qokf = np.asarray(res[5]), np.asarray(res[6])
        out["agg_survivors"] = [float(v) for v in surv[agg_rounds]]
        out["agg_quorum_ok"] = [bool(v > 0) for v in qokf[agg_rounds]]
    return out


# ---------------------------------------------------------------------------
# hierarchical (tier-tree) scan path
# ---------------------------------------------------------------------------

# static tier shape closed over by the compiled hierarchical program:
# per-level member->group maps, group counts, and per-level device
# ancestor maps (all host numpy; they become jit constants)
_HierSpec = collections.namedtuple("_HierSpec",
                                   "group_ids num_groups anc")

# lru_cache keys must be hashable, so the program cache keys on the
# tree FINGERPRINT and the spec arrays ride this side table
_HIER_SPECS: dict = {}


@functools.lru_cache(maxsize=8)
def _hier_program(apply_fn, eta: float, prestage: bool,
                  faults: bool = False, guard: bool = False,
                  quorum: float = 0.0, tree_fp: str = ""):
    """One jitted program per (model, η, staging mode, fault config,
    tier-tree shape). The per-round aggregation LEVEL arrives as a
    traced xs row, so trees with identical shape but different τ
    chains share one compiled program."""
    spec = _HIER_SPECS[tree_fp]
    vstep = jax.vmap(_device_step_fn(apply_fn, eta))

    def train(W0, wg0, x_tr, xb_all, idx_all, yb_all, w_all, counts,
              act, is_agg, x_te, y_te, lvl, *fault_ops):
        n = counts.shape[1]
        body = _make_scan_body(apply_fn, vstep, prestage, faults, guard,
                               quorum, x_tr, x_te, y_te, hier=spec)
        carry0 = (W0, wg0, jnp.zeros(n, jnp.float32),
                  jnp.zeros(n, jnp.float32))
        xs = (xb_all, idx_all, yb_all, w_all, counts, act, is_agg)
        xs = xs + tuple(fault_ops) + (lvl,)
        (_, wg, _, _), ys = jax.lax.scan(body, carry0, xs)
        return (wg,) + ys

    return jax.jit(train)


def run_rounds_hierarchical(apply_fn, params, x_tr, y_tr, x_te, y_te,
                            processed, act_all, tau: int, eta: float,
                            max_pts: int, *, tree, faults=None,
                            guard: bool = True,
                            quorum: float = 0.0) -> dict:
    """Tier-aware window scan over a :class:`repro.core.hierarchy.
    TierTree`: local SGD every round, and at each round whose index
    hits a tier period the eq. (4) aggregation composes UP the tree —
    devices to gateways, gateways to regional groups, … — with devices
    syncing from their ancestor at the round's highest aggregating
    tier. H accumulates across sub-tier windows and resets once the
    top tier consumes it, so the top-tier model telescopes to the flat
    eq. (4) over all contributing devices. The global history
    (test_loss / test_acc / H_agg / agg_round) is reported at TOP-tier
    rounds; ``tier_agg_round``/``tier_agg_level`` record the full
    per-tier cadence.

    An L=1 tree delegates to :func:`run_rounds_scan` — the same
    lru-cached flat program, so the collapse is bitwise by
    construction (the contract ``tests/test_hierarchy.py`` pins).

    ``faults`` ride exactly as on the flat path (crash outages ANDed
    into activity, guarded uploads at the DEVICE tier, quorum gating
    the whole composed event)."""
    if tau != tree.taus[0]:
        raise ValueError(f"run tau={tau} but the tier tree aggregates "
                         f"its first tier every {tree.taus[0]}")
    if tree.levels == 1:
        return run_rounds_scan(apply_fn, params, x_tr, y_tr, x_te, y_te,
                               processed, act_all, tau, eta, max_pts,
                               faults=faults, guard=guard, quorum=quorum)
    if isinstance(processed, pl.FlatStreams):
        T, n = processed.T, processed.n
    else:
        T, n = len(processed), len(processed[0])
    if n != tree.n:
        raise ValueError(f"run has n={n} devices but the tree has "
                         f"n={tree.n}")
    idx, yb, wts, counts = pl.stage_rounds(processed, y_tr, max_pts)

    t_tier0 = time.perf_counter()
    lvl = tree.level_rounds(T)
    is_agg = lvl > 0
    fp = tree.fingerprint()
    if fp not in _HIER_SPECS:
        _HIER_SPECS[fp] = _HierSpec(group_ids=tree.parents,
                                    num_groups=tree.group_counts,
                                    anc=tree.ancestors())
    add_phase_time("tier_agg_s", time.perf_counter() - t_tier0)

    use_faults = faults is not None
    act_arr = np.asarray(act_all)
    fault_ops = ()
    if use_faults:
        act_arr = np.asarray(act_all, bool) & faults.activity_mask()
        fault_ops = _stage_fault_ops(faults, T, n, tau)
    guard_f = bool(guard) if use_faults else False
    quorum_f = float(quorum) if use_faults else 0.0

    x_dev = _to_device_cached(x_tr)
    idx_dev = jnp.asarray(idx)
    item_bytes = int(np.prod(x_tr.shape[1:], dtype=np.int64)) * 4
    prestage = T * n * max_pts * item_bytes <= PRESTAGE_LIMIT_BYTES
    if prestage:
        xb_all, idx_arg = jnp.take(x_dev, idx_dev, axis=0), None
    else:
        xb_all, idx_arg = None, idx_dev

    args = (x_dev, xb_all, idx_arg, jnp.asarray(yb), jnp.asarray(wts),
            jnp.asarray(counts), jnp.asarray(act_arr, jnp.float32),
            jnp.asarray(is_agg), _to_device_cached(x_te),
            _to_device_cached(y_te), jnp.asarray(lvl))

    fn = _hier_program(apply_fn, float(eta), prestage, use_faults,
                       guard_f, quorum_f, fp)
    with sanitize.hot_loop_guard():
        res = fn(_stack(params, n), params, *args, *fault_ops)
        losses, tl, ta, H_at = res[1:5]
        jax.block_until_ready(losses)
    top = np.nonzero(lvl == tree.levels)[0]
    tl, ta, H_at = np.asarray(tl), np.asarray(ta), np.asarray(H_at)
    out = {"device_loss": list(np.asarray(losses)),
           "test_loss": [float(v) for v in tl[top]],
           "test_acc": [float(v) for v in ta[top]],
           "agg_round": [int(t) for t in top],
           "H_agg": list(H_at[top]),
           "tier_agg_round": [int(t) for t in np.nonzero(is_agg)[0]],
           "tier_agg_level": [int(v) for v in lvl[is_agg]]}
    if use_faults:
        surv, qokf = np.asarray(res[5]), np.asarray(res[6])
        out["agg_survivors"] = [float(v) for v in surv[top]]
        out["agg_quorum_ok"] = [bool(v > 0) for v in qokf[top]]
    return out


def _run_scan_checkpointed(apply_fn, params, n, T, tau, eta, prestage,
                           args, fault_ops, use_faults, guard, quorum,
                           checkpoint_path, checkpoint_every, resume,
                           stop_after):
    """Window-chunked scan with checkpoint/resume (see
    ``run_rounds_scan``). History arrays are carried at full (T, ...)
    shape inside the snapshot so the restore template is shape-static;
    the ``round`` scalar says how much of them is real."""
    from repro.checkpoint import checkpoint as ckpt

    step = max(1, int(checkpoint_every)) * tau
    carry = (_stack(params, n), params, jnp.zeros(n, jnp.float32),
             jnp.zeros(n, jnp.float32))
    hist = {"losses": np.zeros((T, n), np.float32),
            "tl": np.zeros(T, np.float32),
            "ta": np.zeros(T, np.float32),
            "H_at": np.zeros((T, n), np.float32)}
    if use_faults:
        hist["surv"] = np.zeros(T, np.float32)
        hist["qok"] = np.ones(T, np.float32)

    def _as_state(carry, hist, rnd):
        W, wg, H, waiting = carry
        return {"carry": {"W": W, "wg": wg, "H": H, "waiting": waiting},
                "hist": hist, "round": np.asarray(rnd, np.int64)}

    run_meta = {"kind": "fog-scan", "T": int(T), "n": int(n),
                "tau": int(tau), "eta": float(eta),
                "faults": bool(use_faults), "guard": bool(guard),
                "quorum": float(quorum)}
    start = 0
    if resume is not None:
        state, meta = ckpt.restore(resume, _as_state(carry, hist, 0))
        for k, v in run_meta.items():
            if meta.get(k) != v:
                raise ValueError(
                    f"checkpoint {resume!r} was written by a run with "
                    f"{k}={meta.get(k)!r}; this run has {k}={v!r}")
        start = int(state["round"])
        c = state["carry"]
        carry = (c["W"], c["wg"], c["H"], c["waiting"])
        hist = {k: np.array(v) for k, v in state["hist"].items()}

    fn = _scan_chunk_program(apply_fn, float(eta), prestage, use_faults,
                             guard, quorum)
    (x_dev, xb_all, idx_arg, yb, wts, counts, act, is_agg, x_te,
     y_te) = args
    keys = ["losses", "tl", "ta", "H_at"] + (
        ["surv", "qok"] if use_faults else [])
    t0 = start
    while t0 < T:
        if stop_after is not None and t0 >= stop_after:
            break
        t1 = min(t0 + step, T)
        sl = slice(t0, t1)
        with sanitize.hot_loop_guard():
            carry, ys = fn(
                carry, x_dev,
                None if xb_all is None else xb_all[sl],
                None if idx_arg is None else idx_arg[sl],
                yb[sl], wts[sl], counts[sl], act[sl], is_agg[sl], x_te,
                y_te, *(op[sl] for op in fault_ops))
        for k, y in zip(keys, ys):
            hist[k][sl] = np.asarray(y)
        t0 = t1
        if checkpoint_path is not None:
            ckpt.save(checkpoint_path, _as_state(carry, hist, t0),
                      metadata=run_meta)

    is_agg_np = np.asarray(is_agg)
    agg_rounds = np.nonzero(is_agg_np[:t0])[0]
    out = {"device_loss": list(hist["losses"][:t0]),
           "test_loss": [float(v) for v in hist["tl"][agg_rounds]],
           "test_acc": [float(v) for v in hist["ta"][agg_rounds]],
           "agg_round": [int(t) for t in agg_rounds],
           "H_agg": list(hist["H_at"][agg_rounds])}
    if use_faults:
        out["agg_survivors"] = [float(v) for v in hist["surv"][agg_rounds]]
        out["agg_quorum_ok"] = [bool(v > 0) for v in hist["qok"][agg_rounds]]
    if t0 < T:
        out["stopped_at"] = int(t0)
    return out


# ---------------------------------------------------------------------------
# device-sharded path (shard_map over the fog-device axis)
# ---------------------------------------------------------------------------


class AsyncEvaluator:
    """Streams test evaluation off the training hot path.

    ``submit`` dispatches one jitted eval and returns immediately (JAX
    async dispatch — nothing blocks until ``collect``), so a sweep can
    keep training the next scenario while eval results trickle from
    device to host. ``submit_stack`` evaluates a whole STACK of
    parameter snapshots (e.g. the (S, windows) grid of a scenario
    bucket) in one vmapped dispatch, so one evaluator drains an entire
    bucket's eval queue. The test set is pinned device-resident;
    submissions hold device arrays only.

    Error handling: a failure while dispatching (trace/compile errors)
    or while the device computation resolves is never swallowed — it is
    deferred and re-raised, with the original exception chained, at the
    next ``collect()``/``result()``/``shutdown()``. Transient dispatch
    failures are retried ``retries`` times with capped exponential
    backoff first; only a dispatch that fails every attempt is
    deferred. ALL accumulated failures are listed in the raised error
    (``.failures``), not just the first. ``submit`` after a deferred
    failure is a no-op so a sweep loop fails once, at the
    synchronization point, instead of crashing mid-dispatch;
    ``shutdown`` is idempotent, including after a raised ``collect``.
    """

    def __init__(self, apply_fn, x_te, y_te, *, retries: int = 3,
                 backoff: float = 0.05, backoff_cap: float = 1.0):
        self._apply = apply_fn
        self._fn = _eval_program(apply_fn)
        self._x = _to_device_cached(x_te)
        self._y = _to_device_cached(y_te)
        self._pending: list = []
        self._errors: list[BaseException] = []
        self._retries = max(0, int(retries))
        self._backoff = float(backoff)
        self._backoff_cap = float(backoff_cap)
        self._closed = False

    def _dispatch(self, fn, *args) -> None:
        """Dispatch with capped exponential backoff; a failure that
        survives every retry is deferred to ``collect()``."""
        delay = self._backoff
        for attempt in range(self._retries + 1):
            try:
                self._pending.append(fn(*args))
                return
            except Exception as e:
                if attempt == self._retries:
                    self._errors.append(e)
                    return
                time.sleep(min(delay, self._backoff_cap))
                delay *= 2.0

    def submit(self, params) -> None:
        if self._errors:
            return                      # surfaced at the next collect()
        self._closed = False
        self._dispatch(self._fn, params, self._x, self._y)

    def submit_stack(self, params_stack, n_axes: int = 1) -> None:
        """Evaluate a stack of snapshots in ONE dispatch: the leading
        ``n_axes`` axes of every leaf are batch axes (vmapped over the
        pinned test set). The results arrive at ``collect()`` as arrays
        of that batch shape, in submission order."""
        if self._errors:
            return
        self._closed = False
        fn = _eval_stack_program(self._apply, int(n_axes))
        self._dispatch(fn, params_stack, self._x, self._y)

    def collect(self) -> tuple[list, list]:
        """Block once for everything submitted; returns (losses, accs)
        — floats for ``submit`` entries, arrays for ``submit_stack``.

        Re-raises instead of returning partial results: the error lists
        EVERY accumulated dispatch/device failure (also available as
        its ``.failures`` attribute) with the first one chained."""
        errs = list(self._errors)
        losses, accs = [], []
        for item in self._pending:
            try:                        # device errors surface here
                tl, ta = item
                tl, ta = np.asarray(tl), np.asarray(ta)
                losses.append(float(tl) if tl.ndim == 0 else tl)
                accs.append(float(ta) if ta.ndim == 0 else ta)
            except Exception as e:
                errs.append(e)
        self._pending = []
        self._errors = []
        if errs:
            lines = "\n".join(
                f"  [{i}] {type(e).__name__}: {e}"
                for i, e in enumerate(errs))
            exc = RuntimeError(
                f"AsyncEvaluator: {len(errs)} submitted evaluation(s) "
                f"failed:\n{lines}")
            exc.failures = tuple(errs)
            raise exc from errs[0]
        return losses, accs

    def result(self) -> tuple[list[float], list[float]]:
        """Alias of :meth:`collect` (blocking result with propagation)."""
        return self.collect()

    def shutdown(self) -> None:
        """Drain everything pending; re-raise any deferred failure.
        Idempotent: a second call (e.g. from a finally block after a
        raised ``collect``) is a no-op."""
        if self._closed:
            return
        self._closed = True
        self.collect()


@functools.lru_cache(maxsize=8)
def _eval_program(apply_fn):
    def ev(p, x, y):
        logits = apply_fn(p, x)
        return mm.ce_loss(logits, y), mm.accuracy(logits, y)

    return jax.jit(ev)


@functools.lru_cache(maxsize=8)
def _eval_stack_program(apply_fn, n_axes: int):
    def ev(p, x, y):
        logits = apply_fn(p, x)
        return mm.ce_loss(logits, y), mm.accuracy(logits, y)

    fn = ev
    for _ in range(n_axes):             # vmap the leading snapshot axes
        fn = jax.vmap(fn, in_axes=(0, None, None))
    return jax.jit(fn)


# Scenario-batched / sharded bucket programs, keyed by
# (apply_fn, eta, staging mode, mesh) — an inspectable ordered dict
# (not an opaque lru_cache) so ``batched_compile_count`` can sum the
# per-shape jit cache sizes: the "one compiled program per shape
# bucket" guarantee is asserted by tests and stamped into bench
# artifacts. LRU-capped like the device cache, so a long-lived serving
# process sweeping many (model, η) combinations does not accumulate
# compiled executables unboundedly.
_BUCKET_PROGRAMS_CAP = 16
_BUCKET_PROGRAMS: collections.OrderedDict = collections.OrderedDict()

# programs compiled by bucket programs that have since been LRU-evicted
# (keeps batched_compile_count monotone for delta-based checks)
_EVICTED_BUCKET_COMPILES = 0


def _program_cache_size(fn) -> int:
    """Per-shape executable count of one jitted program; 0 when the
    (private) jit cache introspection API is unavailable."""
    try:
        return fn._cache_size()
    except AttributeError:
        return 0


def batched_compile_count() -> int:
    """Number of XLA programs the batched/sharded engine has compiled
    (sum of per-shape jit cache entries across bucket programs, plus
    those of evicted programs); 0 when jit cache introspection is
    unavailable in the installed jax."""
    return _EVICTED_BUCKET_COMPILES + sum(
        _program_cache_size(fn) for fn in _BUCKET_PROGRAMS.values())


def _bucket_program(apply_fn, eta: float, prestage: bool, mesh,
                    faults: bool = False, guard: bool = False,
                    quorum: float = 0.0, staging: str = "dense"):
    """One program per (model, η, staging mode, mesh, fault config) —
    jit retraces once per shape bucket, so a whole sweep compiles
    #buckets programs.

    ``staging="ragged"`` swaps the per-round device slabs for the
    chunk-row tables of ``pipeline.stage_scenario_ragged``: each round
    gathers the (R_b, C) rows' owner parameters off the flat (S·n)
    device stack, runs one vmapped value_and_grad over rows, and
    segment-reduces losses/gradients back onto their devices (phantom
    rows land in the trash segment S·n). Per-round work is then
    proportional to the bucket's ACTUAL sample total instead of
    S·n·P_max. Everything outside the round body — windows, deferred
    aggregation, faults, quorum, sync — is byte-for-byte the dense
    trace, because the device axis stays (S, n). Ragged mode is
    single-program only (mesh must be None); its bitwise guarantee is
    in-bucket == alone under RAGGED staging (the CPU scatter-add
    applies row updates in row order, which is extent-independent per
    segment), not equality with the dense slab reduction.

    The scenario axis S leads every operand and is vmapped; inside a
    mesh (``mesh`` not None) the fog-device axis n is additionally
    partitioned across the 1-D "data" mesh via ``shard_map`` and the
    every-τ H-weighted aggregation is a cross-shard ``psum``.

    The round axis is scanned as (T/τ, τ) aggregation windows with a
    DOUBLE-BUFFERED aggregation carry: window w's epilogue only ISSUES
    the H-weighted parameter sums (the psum, on the sharded path) and
    parks them in the carry; the divide + synchronization land in
    window w+1's prologue, next to that window's batch gather and first
    local-SGD dispatch. With the outer scan unrolled by 2 on the mesh
    path, the collective of window w and the independent head of window
    w+1 sit in one XLA block, so a latency-hiding scheduler can overlap
    them; the arithmetic is unchanged (same sums, same divide, same
    order), keeping the path numerically identical to the inline
    aggregation of ``run_rounds_scan``.

    With ``faults`` the per-window operands gain the window-last
    (upload_ok, corrupt) fault views and the epilogue issues GUARDED
    sums (missing/non-finite uploads masked out of the contributing
    set before the fixed-order reduction) plus the psum'd
    survivor/expected counts; the quorum decision — like the divide —
    is deferred to the NEXT prologue, where it gates the finalize, the
    sync, the waiting update and the H reset (which moves from the
    epilogue to the prologue in faults mode only: resetting before the
    next window's first round is positionally different but
    numerically identical, and keeps a quorum-failed window's H
    accumulating). With ``faults=False`` the trace is the historical
    clean program, bit for bit.
    """
    global _EVICTED_BUCKET_COMPILES
    if staging == "ragged" and mesh is not None:
        raise ValueError("ragged staging is single-program only; "
                         "pass mesh=None")
    key = (apply_fn, eta, prestage, mesh, faults, guard, quorum, staging)
    cached = _BUCKET_PROGRAMS.get(key)
    if cached is not None:
        _BUCKET_PROGRAMS.move_to_end(key)
        return cached
    while len(_BUCKET_PROGRAMS) >= _BUCKET_PROGRAMS_CAP:
        _, old = _BUCKET_PROGRAMS.popitem(last=False)   # oldest only
        _EVICTED_BUCKET_COMPILES += _program_cache_size(old)

    # the scenario axis S is carried EXPLICITLY (vmap applied to the
    # per-device step only): the aggregation reduction can then sit
    # behind an optimization_barrier, which has no batching rule but —
    # by pinning the reduction's fusion boundary — keeps its codegen
    # (and therefore its bits) independent of the scenario-axis extent,
    # so batched lanes stay bitwise-equal to per-point runs on CPU
    vstep = jax.vmap(jax.vmap(_device_step_fn(apply_fn, eta)))
    vrow = jax.vmap(_row_loss_fn(apply_fn))
    axis = "data"
    tree_map = jax.tree_util.tree_map
    ragged = staging == "ragged"

    def ragged_round(W, xb, yb, w, cell, cnt, active):
        """One ragged round: differentiate the summed per-row loss
        THROUGH the row-param gather, so the gather's transpose — a
        deterministic row-index-order scatter-add, i.e. exactly the
        ``segment_sum`` reduction — accumulates per-device gradients
        without ever materializing a (rows, param) gradient stack
        (~1.4× faster than the explicit vmap(grad) + segment_sum
        formulation on CPU). Phantom rows carry the trash cell id S·n,
        which the clipped gather maps to row S·n−1: their zero sample
        weights make every contribution a signed zero, and x + ±0.0
        preserves x, so the last device's bits are untouched. The
        per-device loss denominator is the STAGED count (== the dense
        w.sum() exactly, see ``_row_loss_fn``); devices without data
        get loss 0.0 and a zero-scaled update, like the dense step."""
        from repro.kernels import ops

        S_loc, n_loc = cnt.shape
        M = S_loc * n_loc
        denom = jnp.maximum(cnt.reshape(M), 1.0)
        scale = (active * jnp.minimum(cnt, 1.0)).reshape(M)
        Wf = tree_map(lambda p: p.reshape((M,) + p.shape[2:]), W)

        def bucket_loss(Wf):
            Wr = tree_map(lambda p: jnp.take(p, cell, axis=0,
                                             mode="clip"), Wf)
            rloss = vrow(Wr, xb, yb, w)
            return rloss.sum(), rloss

        (_, rloss), g = jax.value_and_grad(bucket_loss,
                                           has_aux=True)(Wf)
        lsum = ops.segment_sum_rows(rloss, cell, num_segments=M + 1)[:M]
        losses = (lsum / denom).reshape(S_loc, n_loc)

        def upd(p, flat, gs):
            sh = (M,) + (1,) * (gs.ndim - 1)
            gs = gs / denom.reshape(sh)
            return (flat - eta * scale.reshape(sh) * gs).reshape(p.shape)

        return tree_map(upd, W, Wf, g), losses

    def agg_sums(W, H, contributing):
        """Numerator/denominator of eq. (4) — psum-reduced on a mesh.

        The weighted sum over the device axis accumulates in FIXED
        index order (0..n-1): unlike an einsum, whose reduction
        strategy (and therefore bits) can change with the scenario-axis
        extent, the sequential accumulation produces the same floats
        for a scenario whether it trains alone or inside a bucket —
        and, since x + 0.0 preserves x exactly, phantom-padded devices
        at the tail leave the real prefix bitwise untouched. The
        fori_loop (rather than an unrolled chain) also keeps XLA from
        contracting the multiply-accumulate into FMAs, whose single
        rounding would drift a ulp from the scan path's einsum."""
        Hc = H * contributing                           # (S, n)
        n_loc = Hc.shape[1]

        def step(i, acc):
            tot, num = acc
            tot = tot + Hc[:, i]
            num = tree_map(
                lambda s, a: s + a[:, i] * Hc[:, i].reshape(
                    (-1,) + (1,) * (a.ndim - 2)), num, W)
            return tot, num

        tot, num = jax.lax.fori_loop(
            0, n_loc, step,
            (jnp.zeros(Hc.shape[0], Hc.dtype),
             tree_map(lambda a: jnp.zeros(
                 (a.shape[0],) + a.shape[2:], a.dtype), W)))
        if mesh is not None:
            num = tree_map(lambda a: jax.lax.psum(a, axis), num)
            tot = jax.lax.psum(tot, axis)
        return num, tot

    def finalize(p_num, p_tot, p_flag, wg):
        """Divide deferred sums into the new global, per scenario."""
        live = (p_flag > 0) & (p_tot > 0)               # (S,)
        return tree_map(
            lambda nm, old: jnp.where(
                live.reshape((-1,) + (1,) * (old.ndim - 1)),
                nm / jnp.maximum(p_tot, 1e-9).reshape(
                    (-1,) + (1,) * (old.ndim - 1)), old),
            p_num, wg)

    def agg_stats(W, H, contributing, upl, cor):
        """Guarded epilogue reduction plus the psum'd survivor and
        expected contributor counts the next prologue's quorum test
        needs (faults mode only)."""
        Wu, contrib = _guarded_uploads(W, contributing, upl, cor,
                                       guard, 2)
        num, tot = agg_sums(Wu, H, contrib)
        surv = contrib.sum(axis=1)                      # (S,)
        expd = contributing.sum(axis=1)                 # (S,)
        if mesh is not None:
            surv = jax.lax.psum(surv, axis)
            expd = jax.lax.psum(expd, axis)
        return num, tot, surv, expd

    def train(W0, wg0, x_tr, xb_all, idx_all, yb_all, w_all, cell_all,
              counts, act, agg_w, *fault_ops):
        def window(carry, xs):
            if faults:
                (W, wg, H, waiting, p_num, p_tot, p_act, p_flag,
                 p_surv, p_expd) = carry
                *rows, cnt, a, agg, upl, cor = xs
                # the quorum decision for the previous window lands
                # here, with its deferred sums: survivors below the
                # quorum fraction kill the whole aggregation event
                qok = p_surv >= quorum * p_expd         # (S,)
                qok_f = qok.astype(jnp.float32)
                p_flag = p_flag * qok_f
            else:
                W, wg, H, waiting, p_num, p_tot, p_act, p_flag = carry
                *rows, cnt, a, agg = xs
            # prologue: REALIZE the aggregation issued by the previous
            # window's epilogue (divide + sync + waiting bookkeeping)
            wg = finalize(p_num, p_tot, p_flag, wg)
            sync_mask = (p_flag > 0)[:, None] & (p_act > 0.5)   # (S, n)
            W = tree_map(
                lambda st, g: jnp.where(
                    sync_mask.reshape(sync_mask.shape
                                      + (1,) * (g.ndim - 1)),
                    g[:, None], st),
                W, wg)
            waiting = jnp.where((p_flag > 0)[:, None],
                                1.0 - p_act, waiting)
            if faults:
                # H reset deferred from the epilogue (see docstring):
                # it must be quorum-gated, and before this window's
                # first round it is numerically identical
                H = jnp.where((p_flag > 0)[:, None],
                              jnp.zeros_like(H), H)
            # waiting only changes at aggregations (window-last rounds
            # by construction), so it is constant inside the window
            act_eff = a * (1.0 - waiting)               # (tau, S, n)

            def round_body(c, rxs):
                W, H = c
                if ragged:
                    xb_r, ridx_r, ryb_r, rw_r, rcell_r, cnt_r, a_r = rxs
                    if not prestage:
                        xb_r = jnp.take(x_tr, ridx_r, axis=0)
                    W, losses = ragged_round(W, xb_r, ryb_r, rw_r,
                                             rcell_r, cnt_r, a_r)
                else:
                    xb_r, idx_r, yb_r, w_r, cnt_r, a_r = rxs
                    if not prestage:
                        xb_r = jnp.take(x_tr, idx_r, axis=0)
                    W, losses = vstep(W, xb_r, yb_r, w_r, a_r)
                return (W, H + cnt_r * a_r), losses

            (W, H), losses = jax.lax.scan(
                round_body, (W, H), tuple(rows) + (cnt, act_eff))
            # epilogue: ISSUE this window's H-weighted sums; consumption
            # is deferred to the next prologue (double-buffered carry),
            # so on the sharded path the cross-shard psum of window w
            # can overlap the gather + first local steps of window w+1
            H_snap = H
            if faults:
                num, tot, surv, expd = jax.lax.optimization_barrier(
                    agg_stats(W, H, act_eff[-1], upl, cor))
                carry = (W, wg, H, waiting, num, tot, a[-1], agg,
                         surv, expd)
                return carry, (losses, H_snap, wg, p_surv, p_expd,
                               qok_f)
            num, tot = jax.lax.optimization_barrier(
                agg_sums(W, H, act_eff[-1]))
            H = jnp.where((agg > 0)[:, None], jnp.zeros_like(H), H)
            carry = (W, wg, H, waiting, num, tot, a[-1], agg)
            return carry, (losses, H_snap, wg)

        S = counts.shape[2]
        n_loc = counts.shape[3]
        zeros = jnp.zeros((S, n_loc), jnp.float32)
        carry0 = (W0, wg0, zeros, zeros,
                  tree_map(jnp.zeros_like, wg0), jnp.zeros(S, jnp.float32),
                  zeros, jnp.zeros(S, jnp.float32))
        if faults:
            carry0 = carry0 + (jnp.zeros(S, jnp.float32),
                               jnp.zeros(S, jnp.float32))
        xs = (xb_all, idx_all, yb_all, w_all)
        if ragged:
            xs = xs + (cell_all,)
        xs = xs + (counts, act, agg_w) + tuple(fault_ops)
        carry, ys = jax.lax.scan(
            window, carry0, xs, unroll=2 if mesh is not None else 1)
        # the ys entry of window w is the global params BEFORE its
        # aggregation realizes; shift by one and realize the final
        # pending window so wg_win[w] is the post-aggregation global
        if faults:
            losses, H_w, wg_ys, surv_ys, expd_ys, qok_ys = ys
            (_, wg, _, _, p_num, p_tot, _, p_flag, p_surv,
             p_expd) = carry
            qok_last = (p_surv >= quorum * p_expd).astype(jnp.float32)
            wg_last = finalize(p_num, p_tot, p_flag * qok_last, wg)
        else:
            losses, H_w, wg_ys = ys
            _, wg, _, _, p_num, p_tot, _, p_flag = carry
            wg_last = finalize(p_num, p_tot, p_flag, wg)
        wg_win = tree_map(
            lambda ys, last: jnp.concatenate([ys[1:], last[None]], 0),
            wg_ys, wg_last)
        if faults:
            shift = lambda ys, last: jnp.concatenate(
                [ys[1:], last[None]], 0)
            return (losses, H_w, wg_win, shift(surv_ys, p_surv),
                    shift(expd_ys, p_expd), shift(qok_ys, qok_last))
        return losses, H_w, wg_win

    fn = train
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        from repro.distributed.sharding import shard_map

        dev = P(None, axis)                  # (S, n, ...) params stack
        w_dev = P(None, None, None, axis)    # (windows, tau, S, n, ...)
        wl_dev = P(None, None, axis)         # (windows, S, n) fault views
        in_specs = (dev, P(), P(), w_dev, w_dev, w_dev, w_dev, P(),
                    w_dev, w_dev, P())
        out_specs = (w_dev, P(None, None, axis), P())
        if faults:
            in_specs = in_specs + (wl_dev, wl_dev)
            out_specs = out_specs + (P(), P(), P())
        fn = shard_map(fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs)
    fn = jax.jit(fn)
    _BUCKET_PROGRAMS[key] = fn
    return fn


def _pad_axis(a, size: int, axis: int):
    if a.shape[axis] == size:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, size - a.shape[axis])
    return np.pad(a, pad)


# ---------------------------------------------------------------------------
# warm re-staging cache: repeat sweeps (replan studies, fault grids,
# --repeat timing runs) re-enter run_rounds_batched with byte-identical
# streams; staging them again costs host gather/scatter time plus a
# fresh host->device upload per operand. The cache keys the STAGED
# device operands by a fingerprint of the pre-staging inputs (stream
# bytes, activity, fault views, dataset identity, staging/bucket/τ
# config), so a warm re-run reuses the device buffers outright.
# Bytes-capped LRU like the other caches.
# ---------------------------------------------------------------------------
_STAGED_CACHE_LIMIT_BYTES = 512 * 1024 ** 2
_STAGED_CACHE: collections.OrderedDict = collections.OrderedDict()
_STAGED_CACHE_STATS = {"hits": 0, "misses": 0}


def staged_cache_stats() -> dict:
    """{'hits', 'misses'} of the warm re-staging cache (process-wide)."""
    return dict(_STAGED_CACHE_STATS)


def reset_staged_cache() -> None:
    _STAGED_CACHE.clear()
    _STAGED_CACHE_STATS.update(hits=0, misses=0)


def _staged_nbytes(args) -> int:
    return sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(args)
               if hasattr(a, "nbytes"))


def _staged_cache_put(key, args, meta) -> None:
    nbytes = _staged_nbytes(args)
    if nbytes > _STAGED_CACHE_LIMIT_BYTES:
        return                          # larger than the whole cache
    used = sum(e[2] for e in _STAGED_CACHE.values())
    while _STAGED_CACHE and used + nbytes > _STAGED_CACHE_LIMIT_BYTES:
        _, evicted = _STAGED_CACHE.popitem(last=False)
        used -= evicted[2]
    _STAGED_CACHE[key] = (args, meta, nbytes)


def _array_identity(arr) -> tuple:
    """Cheap dataset fingerprint: shape/dtype plus a sampled checksum
    (the `_to_device_cached` convention — sparse in-place edits can
    slip through, engine inputs are treated as immutable)."""
    a = np.asarray(arr)
    flat = a.reshape(-1)
    sample = flat[::max(1, flat.size // 4096)]
    return (a.shape, str(a.dtype),
            float(np.asarray(sample, np.float64).sum()))


def _staged_fingerprint(processed_list, act_list, tau, bucket, staging,
                        max_points, mesh_shape, faults, x_tr, y_tr):
    """blake2b over everything the staged operands are a function of."""
    import hashlib

    h = hashlib.blake2b(digest_size=16)
    mp = None if max_points is None else tuple(int(v) for v in max_points)
    h.update(repr((int(tau), bucket, staging, mp, mesh_shape,
                   _array_identity(x_tr), _array_identity(y_tr))).encode())
    for b, p in enumerate(processed_list):
        lens, ids = pl._cell_table(p)
        h.update(lens.tobytes())
        h.update(np.ascontiguousarray(ids).tobytes())
        h.update(np.ascontiguousarray(
            np.asarray(act_list[b], np.float32)).tobytes())
        f = None if faults is None else faults[b]
        if f is None:
            h.update(b"\x00nofault")
        else:
            for v in f.engine_arrays():
                h.update(np.ascontiguousarray(
                    np.asarray(v, np.float32)).tobytes())
    return h.digest()


# per-phase wall-clock accumulators for the batched path, surfaced in
# bench breakdowns: "stage" covers host staging + fingerprint + upload
# dispatch, "train" the program dispatch + eval drain + history
# assembly ("program"/"eval" are the two big slices inside "train"),
# "tier_agg" the hierarchical plane's host-side slice (tier staging +
# traffic accounting) so bench breakdowns separate intra-tier compute
# from up-tree work. Reset/read around a timed region via accessors.
_PHASE = {"stage_s": 0.0, "program_s": 0.0, "eval_s": 0.0,
          "train_s": 0.0, "tier_agg_s": 0.0}


def phase_timings() -> dict:
    return dict(_PHASE)


def reset_phase_timings() -> None:
    _PHASE.update(stage_s=0.0, program_s=0.0, eval_s=0.0, train_s=0.0,
                  tier_agg_s=0.0)


def add_phase_time(phase: str, seconds: float) -> None:
    """Fold externally-timed work (e.g. the sweep driver's host data
    prep) into a phase accumulator."""
    _PHASE[phase] = _PHASE.get(phase, 0.0) + float(seconds)


def _stage_bucket_operands(processed_list, act_list, y_tr, tau, bucket,
                           staging, max_points, mesh, faults, x_dev,
                           x_tr):
    """Build the staged device operands of one bucket run (everything
    after W0/wg0 and x_tr in the program signature, fault views
    included) plus the host metadata needed to slice histories back
    out. This is the unit the warm re-staging cache memoizes."""
    S = len(processed_list)
    mp = list(max_points) if max_points is not None else None
    item_bytes = int(np.prod(x_tr.shape[1:], dtype=np.int64)) * 4

    if staging == "ragged":
        batch = pl.stage_scenario_ragged(
            processed_list, y_tr, act_list, tau, max_points=mp,
            bucket=bucket)
        _, T_b, n_b, R_b, C = batch.dims
        n_pad = n_b                       # ragged is mesh=None only
        n_win = T_b // tau
        prestage = T_b * R_b * C * item_bytes <= PRESTAGE_LIMIT_BYTES
    else:
        batch = pl.stage_scenario_batch(
            processed_list, y_tr, act_list, tau, max_points=mp,
            bucket=bucket)
        _, T_b, n_b, P_b = batch.dims
        n_pad = n_b
        if mesh is not None:
            ndev = int(np.prod(mesh.devices.shape))
            n_pad = -(-n_b // ndev) * ndev
        n_win = T_b // tau
        prestage = (S * T_b * n_pad * P_b * item_bytes
                    <= PRESTAGE_LIMIT_BYTES)

    def stage(a):
        """(S, T_b, n_b, ...) -> (windows, tau, S, n_pad, ...): scan
        axes lead (outer windows, inner rounds), scenarios inside."""
        a = _pad_axis(np.asarray(a), n_pad, 2)
        a = np.moveaxis(a, 0, 1)                  # (T_b, S, n_pad, ...)
        return np.ascontiguousarray(
            a.reshape(n_win, tau, *a.shape[1:]))

    if staging == "ragged":
        # row tables have no scenario axis — just fold rounds into
        # (windows, tau) scan axes
        def stage_rows(a):
            a = np.asarray(a)
            return np.ascontiguousarray(
                a.reshape(n_win, tau, *a.shape[1:]))

        idx = stage_rows(batch.idx)
        yb, wts = stage_rows(batch.yb), stage_rows(batch.w)
        cell = jnp.asarray(stage_rows(batch.cell))
    else:
        idx = stage(batch.idx)
        yb, wts = stage(batch.yb), stage(batch.w)
        cell = None
    counts, act = stage(batch.counts), stage(batch.act)
    # aggregations land on window-last rounds by construction
    agg_w = np.ascontiguousarray(np.asarray(
        batch.is_agg, np.float32).reshape(S, n_win, tau)[..., -1].T)

    fault_ops = ()
    if faults is not None:
        # identity-initialized window-last fault views (phantom windows
        # and devices stay at the 1.0 no-fault value), filled from each
        # scenario's schedule, staged as (windows, S, n_pad)
        upl_w = np.ones((S, n_win, n_pad), np.float32)
        cor_w = np.ones((S, n_win, n_pad), np.float32)
        for b, f in enumerate(faults):
            if f is None:
                continue
            upl_v, cor_v = f.engine_arrays()        # (T_s, n_s)
            sl = slice(tau - 1, f.T, tau)
            upl_w[b, :f.T // tau, :f.n] = upl_v[sl]
            cor_w[b, :f.T // tau, :f.n] = cor_v[sl]
        fault_ops = (jnp.asarray(np.ascontiguousarray(
            np.moveaxis(upl_w, 0, 1))), jnp.asarray(
            np.ascontiguousarray(np.moveaxis(cor_w, 0, 1))))

    idx_dev = jnp.asarray(idx)
    if prestage:
        xb_all, idx_arg = jnp.take(x_dev, idx_dev, axis=0), None
    else:
        xb_all, idx_arg = None, idx_dev

    staged_args = (xb_all, idx_arg, jnp.asarray(yb), jnp.asarray(wts),
                   cell, jnp.asarray(counts), jnp.asarray(act),
                   jnp.asarray(agg_w)) + fault_ops
    meta = {"T": list(batch.T), "n": list(batch.n),
            "is_agg": np.asarray(batch.is_agg), "T_b": T_b,
            "n_win": n_win, "n_pad": n_pad, "prestage": prestage}
    return staged_args, meta


def run_rounds_batched(apply_fn, params_list, x_tr, y_tr, x_te, y_te,
                       processed_list, act_list, tau: int, eta: float,
                       max_points=None, *, bucket: str = "pow2",
                       mesh="auto", staging: str = "dense", faults=None,
                       guard: bool = True,
                       quorum: float = 0.0) -> list[dict]:
    """Train a whole bucket of scenarios in ONE compiled program.

    ``processed_list``/``act_list``/``params_list`` carry S scenarios
    (possibly of different true (T, n, P) — they are padded up to the
    shared shape bucket with phantom inactive rounds/devices, see
    ``data.pipeline.stage_scenario_batch``); all scenarios must share
    the dataset, model, η and τ. The scenario axis is vmapped over the
    existing window scan; on a multi-device host (``mesh="auto"``) the
    fog-device axis is additionally partitioned across a 1-D "data"
    mesh inside each shard of which the scenario axis is still vmapped,
    with the every-τ aggregation as an H-weighted cross-shard ``psum``
    issued one window early (see ``_bucket_program``). Evaluation of
    the whole (S, windows) snapshot grid streams off the hot path as a
    single :class:`AsyncEvaluator` stacked dispatch.

    Returns one history dict per scenario, each sliced back to its true
    (T, n) and — on CPU — bitwise-identical to running that scenario
    alone through ``run_rounds_scan``.

    ``staging`` — ``"dense"`` (default) stages the classic padded
    (S, T_b, n_b, P_b) slabs; ``"ragged"`` stages the chunk-row tables
    of ``pipeline.stage_scenario_ragged`` so the compiled per-round
    work tracks the bucket's actual sample total (mesh must be None;
    bitwise guarantee: equal to the same scenario run ALONE under
    ragged staging, allclose to the dense/scan paths). Staged device
    operands are memoized across calls in a fingerprint-keyed LRU
    (``staged_cache_stats``), so warm repeat sweeps skip the host
    staging and re-upload entirely.

    ``faults`` — optional list of per-scenario
    :class:`repro.core.faults.FaultSchedule` (entries may be None):
    crash outages are ANDed into each scenario's activity and the
    window-last (upload_ok, corrupt) views ride the window scan, with
    the shared ``guard``/``quorum`` config applied across the bucket
    (see ``run_rounds_scan`` for the semantics).
    """
    t_stage0 = time.perf_counter()
    if staging not in ("dense", "ragged"):
        raise ValueError(f"staging must be 'dense' or 'ragged'; "
                         f"got {staging!r}")
    S = len(processed_list)
    use_faults = faults is not None and any(f is not None for f in faults)
    if use_faults:
        if len(faults) != S:
            raise ValueError(f"faults list has {len(faults)} entries "
                             f"for {S} scenarios")
        act_list = list(act_list)
        for b, f in enumerate(faults):
            if f is None:
                continue
            T_s, n_s = len(processed_list[b]), len(processed_list[b][0])
            _stage_fault_ops(f, T_s, n_s, tau)     # dims validation
            act_list[b] = np.asarray(act_list[b], bool) \
                & f.activity_mask()
    guard_f = bool(guard) if use_faults else False
    quorum_f = float(quorum) if use_faults else 0.0

    if mesh == "auto":
        mesh = None
        if jax.device_count() > 1:
            from repro.launch.mesh import data_mesh_for

            n_max = max(
                p.n if isinstance(p, pl.FlatStreams) else len(p[0])
                for p in processed_list)
            mesh = data_mesh_for(pl.bucket_size(
                n_max, bucket, max_inflation=pl.BUCKET_MAX_INFLATION))
    if staging == "ragged" and mesh is not None:
        raise ValueError("ragged staging is single-program only; "
                         "pass mesh=None (or staging='dense')")

    mesh_shape = None if mesh is None else tuple(mesh.devices.shape)
    x_dev = _to_device_cached(x_tr)
    cache_key = _staged_fingerprint(
        processed_list, act_list, tau, bucket, staging, max_points,
        mesh_shape, faults if use_faults else None, x_tr, y_tr)
    hit = _STAGED_CACHE.get(cache_key)
    if hit is not None:
        _STAGED_CACHE.move_to_end(cache_key)
        _STAGED_CACHE_STATS["hits"] += 1
        staged_args, meta, _ = hit
    else:
        _STAGED_CACHE_STATS["misses"] += 1
        staged_args, meta = _stage_bucket_operands(
            processed_list, act_list, y_tr, tau, bucket, staging,
            max_points, mesh, faults if use_faults else None, x_dev,
            x_tr)
        _staged_cache_put(cache_key, staged_args, meta)
    n_pad = meta["n_pad"]
    T_b, n_win = meta["T_b"], meta["n_win"]

    # parameter stacks staged host-side: one device put per leaf
    # instead of per-(bucket shape) broadcast/stack mini-programs.
    tree_map = jax.tree_util.tree_map
    W0 = tree_map(
        lambda *ps: jnp.asarray(np.stack([np.broadcast_to(
            np.asarray(p), (n_pad, *p.shape)) for p in ps])),
        *params_list)
    wg0 = tree_map(
        lambda *ps: jnp.asarray(np.stack([np.asarray(p) for p in ps])),
        *params_list)

    t_train0 = time.perf_counter()
    _PHASE["stage_s"] += t_train0 - t_stage0
    fn = _bucket_program(apply_fn, float(eta), meta["prestage"], mesh,
                         use_faults, guard_f, quorum_f, staging)
    with sanitize.hot_loop_guard():
        res = fn(W0, wg0, x_dev, *staged_args)
        jax.block_until_ready(res)
    t_eval0 = time.perf_counter()
    _PHASE["program_s"] += t_eval0 - t_train0
    losses, H_w, wg_win = res[:3]
    if use_faults:
        surv_win, expd_win, qok_win = (np.asarray(r) for r in res[3:])

    # one stacked eval dispatch drains the whole bucket's (windows, S)
    # snapshot grid off the hot path; per-scenario agg windows are
    # selected host-side (phantom windows' results are simply unused)
    ev = AsyncEvaluator(apply_fn, x_te, y_te)
    ev.submit_stack(wg_win, n_axes=2)
    (tl,), (ta,) = ev.collect()
    _PHASE["eval_s"] += time.perf_counter() - t_eval0

    losses = np.asarray(losses).reshape(T_b, S, n_pad)
    H_w = np.asarray(H_w)
    hists = []
    for b in range(S):
        T, n = meta["T"][b], meta["n"][b]
        agg_rounds = np.nonzero(meta["is_agg"][b, :T])[0]
        wins = agg_rounds // tau
        h = {
            "device_loss": list(losses[:T, b, :n]),
            "test_loss": [float(v) for v in tl[wins, b]],
            "test_acc": [float(v) for v in ta[wins, b]],
            "agg_round": [int(t) for t in agg_rounds],
            "H_agg": list(H_w[wins, b][:, :n])}
        if use_faults:
            h["agg_survivors"] = [float(v) for v in surv_win[wins, b]]
            h["agg_quorum_ok"] = [bool(v > 0) for v in qok_win[wins, b]]
        hists.append(h)
    _PHASE["train_s"] += time.perf_counter() - t_train0
    return hists


def run_rounds_batched_single(apply_fn, params, x_tr, y_tr, x_te, y_te,
                              processed, act_all, tau: int, eta: float,
                              max_pts: int, *, mesh="auto",
                              staging: str = "dense", faults=None,
                              guard: bool = True,
                              quorum: float = 0.0) -> dict:
    """Single-scenario entry to the batched path (``engine="batched"``
    with S=1): same program structure, exact pad sizes."""
    return run_rounds_batched(
        apply_fn, [params], x_tr, y_tr, x_te, y_te, [processed],
        [act_all], tau, eta, [max_pts], bucket="exact", mesh=mesh,
        staging=staging,
        faults=None if faults is None else [faults], guard=guard,
        quorum=quorum)[0]


def run_rounds_sharded(apply_fn, params, x_tr, y_tr, x_te, y_te, processed,
                       act_all, tau: int, eta: float, max_pts: int, *,
                       mesh=None, faults=None, guard: bool = True,
                       quorum: float = 0.0) -> dict:
    """Device-sharded scan: the n fog devices are partitioned across the
    mesh's "data" axis; n is padded up to a mesh multiple with phantom
    always-inactive devices (zero weights and counts — they never train,
    contribute H=0 and are masked out of every aggregation). The round
    axis is padded to a multiple of tau and scanned as (T/tau, tau)
    aggregation windows (padded rounds are inactive and non-agg, so
    they train nothing). Matches ``run_rounds_scan`` up to cross-shard
    reduction reassociation; eval is streamed off the hot path via
    :class:`AsyncEvaluator` from the per-window parameter snapshots.

    Since the batched plane landed this is the S=1 slice of
    ``run_rounds_batched``: same bucket program, same double-buffered
    overlapped-psum aggregation windows."""
    from repro.launch.mesh import make_data_mesh

    if mesh is None:
        mesh = make_data_mesh()
    return run_rounds_batched(
        apply_fn, [params], x_tr, y_tr, x_te, y_te, [processed],
        [act_all], tau, eta, [max_pts], bucket="exact", mesh=mesh,
        faults=None if faults is None else [faults], guard=guard,
        quorum=quorum)[0]


# ---------------------------------------------------------------------------
# legacy per-round loop (numerical oracle + benchmark baseline)
# ---------------------------------------------------------------------------


def run_rounds_legacy(apply_fn, params, x_tr, y_tr, x_te, y_te, processed,
                      act_all, tau: int, eta: float, max_pts: int, *,
                      faults=None, guard: bool = True,
                      quorum: float = 0.0) -> dict:
    """The original per-round dispatch loop (fresh host→device copies of
    the padded batch every round). ``faults``/``guard``/``quorum`` give
    the compiled paths their numerical oracle under fault injection
    (see ``run_rounds_scan``)."""
    T = len(processed)
    n = len(processed[0])
    W = _stack(params, n)
    w_global = params
    step = make_device_step(apply_fn, eta)
    eval_fn = jax.jit(lambda p, x, y: (
        mm.ce_loss(apply_fn(p, x), y), mm.accuracy(apply_fn(p, x), y)))

    act_arr = np.asarray(act_all)
    upl = cor = None
    if faults is not None:
        upl, cor = (np.asarray(v) for v in
                    _stage_fault_ops(faults, T, n, tau))
        act_arr = np.asarray(act_all, bool) & faults.activity_mask()

    H = np.zeros(n)
    waiting = np.zeros(n, bool)
    out = {"device_loss": [], "test_loss": [], "test_acc": [],
           "agg_round": [], "H_agg": []}
    if faults is not None:
        out["agg_survivors"] = []
        out["agg_quorum_ok"] = []
    for t in range(T):
        act = np.asarray(act_arr[t], bool)
        xb, yb, wts = pl.pad_batches(processed[t], x_tr, y_tr, max_pts)
        W, losses = step(W, jnp.asarray(xb), jnp.asarray(yb),
                         jnp.asarray(wts),
                         jnp.asarray(act & ~waiting, jnp.float32))
        H += np.array([len(ix) for ix in processed[t]]) * (act & ~waiting)
        out["device_loss"].append(np.asarray(losses))

        if (t + 1) % tau == 0:
            contributing = jnp.asarray(act & ~waiting, jnp.float32)
            if faults is not None:
                Wu, contrib = _guarded_uploads(
                    W, contributing, jnp.asarray(upl[t]),
                    jnp.asarray(cor[t]), guard, 1)
                surv = float(contrib.sum())
                expd = float(contributing.sum())
                qok = surv >= quorum * expd
                out["agg_survivors"].append(surv)
                out["agg_quorum_ok"].append(bool(qok))
                out["H_agg"].append(H.copy())
                if qok:
                    w_global = aggregate(Wu, jnp.asarray(H, jnp.float32),
                                         contrib, w_global)
                    W = _sync(W, w_global, jnp.asarray(act))
                    waiting = ~act
                    H[:] = 0.0
            else:
                w_global = aggregate(W, jnp.asarray(H, jnp.float32),
                                     contributing, w_global)
                W = _sync(W, w_global, jnp.asarray(act))
                waiting = ~act      # whoever is out now waits for next sync
                out["H_agg"].append(H.copy())
                H[:] = 0.0
            tl_, ta_ = eval_fn(w_global, jnp.asarray(x_te), jnp.asarray(y_te))
            out["agg_round"].append(t)
            out["test_loss"].append(float(tl_))
            out["test_acc"].append(float(ta_))
    return out
