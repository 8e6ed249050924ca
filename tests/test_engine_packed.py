"""Packed local SGD in the scan engine: the chunk-row staging
(``pipeline.stage_rounds_scan``) and its selection rule, and the packed
programs against the dense (T, n, P) program and the legacy loop on a
skewed stream, with and without faults, checkpointed and hierarchical."""
import warnings

import jax
import numpy as np
import pytest

from repro.core import engine as eng
from repro.core import faults as fl
from repro.core import hierarchy as hr
from repro.core import monitoring
from repro.data import pipeline as pl
from repro.data.synthetic import make_image_dataset


def _skewed(n=4, T=4, heavy=40, light=6, seed=0, n_train=400):
    """Per-cell streams where device 1 holds most samples of every round
    and the others hold 0..light (some cells empty) — offloading's shape
    in the paper's CNN cell."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n_train)
    processed, k = [], 0
    for _ in range(T):
        row = []
        for i in range(n):
            c = heavy if i == 1 else int(rng.integers(0, light + 1))
            row.append(perm[k:k + c].astype(np.int64))
            k += c
        processed.append(row)
    return processed


# ---------------------------------------------------------------------------
# staging
# ---------------------------------------------------------------------------


@pytest.fixture
def chunk(request, monkeypatch):
    """The packed rows' chunk ``pl.PACKED_CHUNK``, set to the test's
    parameter."""
    monkeypatch.setattr(pl, "PACKED_CHUNK", request.param)
    return request.param


@pytest.mark.parametrize("chunk", [16], indirect=True)
def test_every_sample_lands_once_in_a_row_of_its_device(chunk):
    processed = _skewed(n=5, T=6, heavy=150, light=40)
    y = np.arange(400) % 10
    P = pl.pad_size(processed)
    idx, yb, w, cell, counts = pl.stage_rounds_scan(processed, y, P)
    T, R, C = idx.shape
    for t in range(T):
        for i in range(5):
            mine = (cell[t] == i)[:, None] & (w[t] > 0)
            # in stream order: rows of a device are consecutive, chunk-major
            np.testing.assert_array_equal(idx[t][mine], processed[t][i])
            np.testing.assert_array_equal(yb[t][mine], y[processed[t][i]])
            assert counts[t, i] == len(processed[t][i])
    assert int(w.sum()) == sum(len(ix) for row in processed for ix in row)
    assert set(np.unique(w)) <= {0.0, 1.0}


@pytest.mark.parametrize("chunk", [16], indirect=True)
def test_phantom_rows_have_weight_zero(chunk):
    processed = _skewed(n=5, T=6, heavy=150, light=40)
    idx, yb, w, cell, counts = pl.stage_rounds_scan(
        processed, np.zeros(400, np.int64), pl.pad_size(processed))
    phantom = cell == 5
    assert phantom.any()
    assert not w[phantom].any()
    assert (cell <= 5).all() and (cell >= 0).all()


@pytest.mark.parametrize("chunk", [8], indirect=True)
def test_row_bucket_is_pow2_without_the_inflation_cap(chunk):
    # 9 rows in the busiest round: the capped bucket would keep 9
    processed = [[np.arange(72), np.arange(0)], [np.arange(8),
                                                 np.arange(8, 16)]]
    assert pl.bucket_size(9, max_inflation=pl.BUCKET_MAX_INFLATION) == 9
    idx, yb, w, cell, counts = pl.stage_rounds_scan(
        processed, np.zeros(100, np.int64), 200)
    assert idx.shape == (2, 16, 8)
    np.testing.assert_array_equal(counts, [[72, 0], [8, 8]])


def _mlp_like(rng, n=10, T=20):
    """The MLP cell under capacity 60: every cell <= 60, pad 64."""
    return [[np.arange(rng.integers(30, 61)) for _ in range(n)]
            for _ in range(T)]


def _assert_dense_like_stage_rounds(processed, y, P):
    idx, yb, w, cell, counts = pl.stage_rounds_scan(processed, y, P)
    assert cell is None
    for u, v in zip((idx, yb, w, counts), pl.stage_rounds(processed, y, P)):
        assert u.dtype == v.dtype
        np.testing.assert_array_equal(u, v)


def test_rule_packs_a_skewed_cnn_stream_and_keeps_mlp_shapes_dense():
    """The paper's CNN cell after offloading: one device holds ~600 of a
    round's samples, a few others 20-40, pad 768 — packed at the
    module's chunk. The MLP cell's shapes stay dense, staged exactly as
    ``stage_rounds`` stages them."""
    rng = np.random.default_rng(1)
    n, T = 10, 20
    cnn = [[np.arange(rng.integers(600, 680)) if i == 5 else
            np.arange(rng.integers(20, 41) if i in (0, 2, 7) else 0)
            for i in range(n)] for _ in range(T)]
    y = np.arange(1000) % 10
    idx, yb, w, cell, counts = pl.stage_rounds_scan(cnn, y, 768)
    assert cell is not None
    T_, R, C = idx.shape
    assert C == pl.PACKED_CHUNK and R * C < n * 768
    _assert_dense_like_stage_rounds(_mlp_like(rng), y, 64)


@pytest.mark.parametrize("chunk", [32, 64, 256], indirect=True)
def test_mlp_shapes_stay_dense_at_large_chunks(chunk):
    """At C >= 32 every non-empty cell of 30-60 samples takes a row of
    at least 32 slots: the packed rows cannot go under n·P = 640."""
    rng = np.random.default_rng(chunk)
    y = np.arange(1000) % 10
    mlp = _mlp_like(rng)
    _assert_dense_like_stage_rounds(mlp, y, 64)
    flat = pl.flat_from_streams(pl.FogStreams(collected=mlp, n=10, T=20))
    _assert_dense_like_stage_rounds(flat, y, 64)
    with warnings.catch_warnings():     # a pad below the cells truncates
        warnings.simplefilter("ignore")
        _assert_dense_like_stage_rounds(mlp, y, 50)


def test_packed_keeps_what_dense_staging_keeps(monkeypatch):
    """Flat streams stage like their per-cell lists; a pad below a cell
    truncates it to its first P samples, as ``stage_rounds`` does."""
    processed = _skewed(n=4, T=3, heavy=50, light=5)
    y = np.arange(400) % 10
    flat = pl.flat_from_streams(pl.FogStreams(collected=processed, n=4,
                                              T=3))
    monkeypatch.setattr(pl, "PACKED_CHUNK", 8)
    a = pl.stage_rounds_scan(processed, y, 64)
    b = pl.stage_rounds_scan(flat, y, 64)
    assert a[3] is not None
    for u, v in zip(a, b):
        np.testing.assert_array_equal(u, v)
    monkeypatch.setattr(pl, "PACKED_CHUNK", 4)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        idx, _, w, cell, counts = pl.stage_rounds_scan(processed, y, 20)
        d_idx, _, d_w, d_counts = pl.stage_rounds(processed, y, 20)
    np.testing.assert_array_equal(counts, d_counts)
    for t in range(3):
        for i in range(4):
            np.testing.assert_array_equal(
                idx[t][((cell[t] == i)[:, None]) & (w[t] > 0)],
                d_idx[t, i][d_w[t, i] > 0])


# ---------------------------------------------------------------------------
# packed programs against the dense program and the legacy loop
# ---------------------------------------------------------------------------

DATA = {}


def _data():
    if not DATA:
        DATA["d"] = make_image_dataset(n_train=400, n_test=100, seed=0)
    return DATA["d"]


def _faults(T, n, tau):
    return fl.FaultSchedule(T, n, tau, [
        fl.FaultEvent(1, "corrupt", 0, float("nan")),
        fl.FaultEvent(2, "crash", 2),
        fl.FaultEvent(3, "drop", 3)])


def _packed_scan(model, processed, tau, faults=None, **kw):
    x_tr, y_tr, x_te, y_te = _data()
    params, apply_fn = eng.make_model(model, jax.random.PRNGKey(0))
    T, n = len(processed), len(processed[0])
    monitoring.reset()
    h = eng.run_rounds_scan(apply_fn, params, x_tr, y_tr, x_te, y_te,
                            processed, np.ones((T, n), bool), tau, 0.1,
                            pl.pad_size(processed), faults=faults,
                            guard=True, quorum=0.3, **kw)
    return h, monitoring.totals()["train.stage"]


def _dense_scan(model, processed, tau, faults=None):
    """The dense program on ``stage_rounds`` operands, called directly."""
    import jax.numpy as jnp

    x_tr, y_tr, x_te, y_te = _data()
    params, apply_fn = eng.make_model(model, jax.random.PRNGKey(0))
    T, n = len(processed), len(processed[0])
    idx, yb, w, counts = pl.stage_rounds(processed, y_tr,
                                         pl.pad_size(processed))
    act = np.ones((T, n), bool)
    fault_ops = ()
    if faults is not None:
        act = act & faults.activity_mask()
        fault_ops = eng._stage_fault_ops(faults, T, n, tau)
    is_agg = (np.arange(T) + 1) % tau == 0
    fn = eng._scan_program(apply_fn, 0.1, False, faults is not None,
                           faults is not None,
                           0.3 if faults is not None else 0.0)
    res = fn(eng._stack(params, n), params, jnp.asarray(x_tr), None,
             *(jnp.asarray(a) for a in (idx, yb, w, counts,
                                        act.astype(np.float32), is_agg)),
             jnp.asarray(x_te), jnp.asarray(y_te), *fault_ops)
    losses, tl, H_at = (np.asarray(res[i]) for i in (1, 2, 4))
    agg = np.nonzero(is_agg)[0]
    return {"device_loss": list(losses), "test_loss": list(tl[agg]),
            "H_agg": list(H_at[agg]), "agg_round": list(agg)}


def _legacy(model, processed, tau, faults=None):
    x_tr, y_tr, x_te, y_te = _data()
    params, apply_fn = eng.make_model(model, jax.random.PRNGKey(0))
    T, n = len(processed), len(processed[0])
    return eng.run_rounds_legacy(apply_fn, params, x_tr, y_tr, x_te, y_te,
                                 processed, np.ones((T, n), bool), tau,
                                 0.1, pl.pad_size(processed),
                                 faults=faults, guard=True, quorum=0.3)


def _assert_close(h, ref, rtol):
    assert list(h["agg_round"]) == list(ref["agg_round"])
    np.testing.assert_array_equal(np.stack(h["H_agg"]),
                                  np.stack(ref["H_agg"]))
    np.testing.assert_allclose(np.stack(h["device_loss"]),
                               np.stack(ref["device_loss"]),
                               rtol=rtol, atol=1e-5)
    np.testing.assert_allclose(h["test_loss"], ref["test_loss"],
                               rtol=rtol, atol=1e-5)


@pytest.fixture
def chunk8(monkeypatch):
    """A chunk that packs the small skewed streams of these tests."""
    monkeypatch.setattr(pl, "PACKED_CHUNK", 8)


@pytest.mark.parametrize("model", ["cnn", "mlp"])
@pytest.mark.parametrize("faulty", [False, True])
def test_packed_scan_matches_dense_program_and_legacy(chunk8, model,
                                                      faulty):
    processed = _skewed()
    T, n, tau = 4, 4, 2
    faults = _faults(T, n, tau) if faulty else None
    h, st = _packed_scan(model, processed, tau, faults)
    assert st["packed"] == 1
    assert st["slots"] < T * n * pl.pad_size(processed)
    # devices without data in a round: loss 0, as on dense slots
    empty = np.array([[len(ix) == 0 for ix in row] for row in processed])
    assert empty.any()
    assert not np.stack(h["device_loss"])[empty].any()
    _assert_close(h, _dense_scan(model, processed, tau, faults), 1e-4)
    _assert_close(h, _legacy(model, processed, tau, faults), 2e-3)
    if faulty:
        assert h["agg_survivors"] == _legacy(model, processed, tau,
                                             faults)["agg_survivors"]


def _assert_bitwise(a, b):
    assert a["agg_round"] == b["agg_round"]
    assert a["test_loss"] == b["test_loss"]
    assert a["test_acc"] == b["test_acc"]
    np.testing.assert_array_equal(np.stack(a["device_loss"]),
                                  np.stack(b["device_loss"]))
    np.testing.assert_array_equal(np.stack(a["H_agg"]),
                                  np.stack(b["H_agg"]))


def test_packed_checkpointed_and_resumed_match_monolithic_bitwise(
        chunk8, tmp_path):
    processed = _skewed(T=8)
    full, st = _packed_scan("mlp", processed, 2)
    assert st["packed"] == 1
    ck = str(tmp_path / "ck.msgpack")
    chunked, _ = _packed_scan("mlp", processed, 2, checkpoint_path=ck,
                              checkpoint_every=1)
    _assert_bitwise(full, chunked)
    _packed_scan("mlp", processed, 2, checkpoint_path=ck, stop_after=4)
    resumed, _ = _packed_scan("mlp", processed, 2, resume=ck)
    _assert_bitwise(full, resumed)


def test_packed_hierarchical_matches_legacy(chunk8):
    """A two-tier tree aggregating both tiers every τ is flat eq. (4)
    regrouped: on packed rows it stays within float tolerance of the
    legacy loop."""
    processed = _skewed(T=4)
    x_tr, y_tr, x_te, y_te = _data()
    params, apply_fn = eng.make_model("mlp", jax.random.PRNGKey(0))
    tree = hr.TierTree.balanced(4, (2, 1), (2, 2))
    monitoring.reset()
    h = eng.run_rounds_scan(
        apply_fn, params, x_tr, y_tr, x_te, y_te, processed,
        np.ones((4, 4), bool), 2, 0.1, pl.pad_size(processed), tree=tree)
    assert monitoring.totals()["train.stage"]["packed"] == 1
    _assert_close(h, _legacy("mlp", processed, 2), 2e-3)


# ---------------------------------------------------------------------------
# the prestaged gather against the in-scan gather
# ---------------------------------------------------------------------------


def _run_engine(kind, processed, tau=2):
    """One run of ``kind`` on the CNN (it reads images, so a wrong
    reshape of the gathered rows cannot pass) and its ``train.stage``
    counters."""
    x_tr, y_tr, x_te, y_te = _data()
    params, apply_fn = eng.make_model("cnn", jax.random.PRNGKey(0))
    T, n = len(processed), len(processed[0])
    act = np.ones((T, n), bool)
    P = pl.pad_size(processed)
    eng.reset_staged_cache()
    monitoring.reset()
    if kind.startswith("batched"):
        h = eng.run_rounds_batched(
            apply_fn, [params], x_tr, y_tr, x_te, y_te, [processed], [act],
            tau, 0.1, mesh=None, staging=kind.split("_")[1])[0]
    elif kind == "hierarchical":
        h = eng.run_rounds_scan(
            apply_fn, params, x_tr, y_tr, x_te, y_te, processed, act, tau,
            0.1, P, tree=hr.TierTree.balanced(n, (2, 1), (tau, 2 * tau)))
    else:
        h = eng.run_rounds_scan(apply_fn, params, x_tr, y_tr, x_te, y_te,
                                processed, act, tau, 0.1, P)
    return h, monitoring.totals()["train.stage"]


@pytest.mark.parametrize("kind", ["scan_dense", "scan_packed", "hierarchical",
                                  "batched_dense", "batched_ragged"])
def test_prestaged_and_in_scan_gathers_hand_the_step_the_same_pixels(
        chunk8, monkeypatch, kind):
    """Both gathers read rows of the (N, 784) view of the (N, 28, 28)
    images and reshape each round's rows to images: the histories are
    the same bits whether the rows were gathered up front or inside the
    program."""
    processed = _skewed(T=4)
    if kind == "scan_dense":
        monkeypatch.setattr(pl, "PACKED_CHUNK", 64)
    assert _data()[0].ndim == 3
    hists = []
    for limit, prestaged in ((0, 0), (1 << 40, 1)):
        monkeypatch.setattr(eng, "PRESTAGE_LIMIT_BYTES", limit)
        h, st = _run_engine(kind, processed)
        assert st["prestaged"] == prestaged
        if kind.startswith("scan"):
            assert st["packed"] == (kind == "scan_packed")
        hists.append(h)
    _assert_bitwise(*hists)
