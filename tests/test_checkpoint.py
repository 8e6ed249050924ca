"""Checkpoint plane (ISSUE-6): msgpack pytree snapshots — provenance
metadata round-trip, every-mismatch-in-one-error restore validation,
atomic save — and the engine's window-boundary checkpoint/resume
(chunked == monolithic bitwise, resume-mid-horizon bitwise, run-meta
guard), on clean and faulted runs."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt
from repro.core import faults as fl
from repro.core import federated as F
from repro.core import movement as mv
from repro.core.costs import synthetic_costs
from repro.core.topology import fully_connected
from repro.data import pipeline as pl
from repro.data.synthetic import make_image_dataset


# ---------------------------------------------------------------------------
# checkpoint module: metadata, validation, atomicity
# ---------------------------------------------------------------------------


def test_metadata_provenance_stamp(tmp_path):
    path = os.path.join(tmp_path, "ck.msgpack")
    tree = {"a": jnp.zeros((2,), jnp.float32)}
    ckpt.save(path, tree, {"step": 3})
    _, meta = ckpt.restore(path, tree)
    assert meta["step"] == 3
    assert meta["jax_version"] == jax.__version__
    assert isinstance(meta["git_sha"], str) and meta["git_sha"]
    assert "saved_at" in meta
    # caller keys win over the auto stamp on collision
    ckpt.save(path, tree, {"git_sha": "pinned"})
    _, meta = ckpt.restore(path, tree)
    assert meta["git_sha"] == "pinned"


def test_restore_reports_every_mismatched_leaf(tmp_path):
    path = os.path.join(tmp_path, "ck.msgpack")
    ckpt.save(path, {"a": jnp.zeros((2,), jnp.float32),
                     "b": jnp.zeros((3,), jnp.float32),
                     "gone": jnp.zeros((1,), jnp.float32)})
    template = {"a": jnp.zeros((4,), jnp.float32),      # shape mismatch
                "b": jnp.zeros((3,), jnp.int32),        # dtype mismatch
                "new": jnp.zeros((1,), jnp.float32)}    # missing leaf
    with pytest.raises(ValueError) as ei:
        ckpt.restore(path, template)
    msg = str(ei.value)
    assert "4 mismatched leaf path(s)" in msg
    assert "'a'" in msg and "(2,)" in msg and "(4,)" in msg
    assert "'b'" in msg and "dtype" in msg
    assert "'new'" in msg and "missing from checkpoint" in msg
    assert "'gone'" in msg and "not in template" in msg


def test_save_is_atomic_on_failure(tmp_path):
    class Exploding:
        def __array__(self, *a, **kw):
            raise RuntimeError("cannot serialize")

    path = os.path.join(tmp_path, "ck.msgpack")
    good = {"a": jnp.arange(3, dtype=jnp.float32)}
    ckpt.save(path, good)
    before = open(path, "rb").read()
    with pytest.raises(Exception):
        ckpt.save(path, {"a": Exploding()})
    # previous snapshot untouched, no temp file left behind
    assert open(path, "rb").read() == before
    assert os.listdir(tmp_path) == [os.path.basename(path)]
    out, _ = ckpt.restore(path, good)
    np.testing.assert_array_equal(out["a"], good["a"])


def test_bfloat16_roundtrip_bitwise(tmp_path):
    path = os.path.join(tmp_path, "ck.msgpack")
    tree = {"w": jnp.asarray(np.random.default_rng(0)
                             .normal(size=17), jnp.bfloat16)}
    ckpt.save(path, tree)
    out, _ = ckpt.restore(path, tree)
    assert out["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(tree["w"]).view(np.uint16),
        np.asarray(out["w"]).view(np.uint16))


# ---------------------------------------------------------------------------
# engine window-boundary checkpoint/resume
# ---------------------------------------------------------------------------


def _setup(n=6, T=12, tau=4, seed=0):
    data = make_image_dataset(n_train=1200, n_test=400, seed=0)
    cfg = F.FedConfig(n=n, T=T, tau=tau, eta=0.05, model="mlp",
                      seed=seed)
    rng = np.random.default_rng(seed)
    traces = synthetic_costs(n, T, rng)
    adj = fully_connected(n)
    streams = pl.poisson_streams(n, T, data[1], rng=rng)
    plan = mv.greedy_linear(traces, adj)
    return cfg, data, traces, adj, plan, streams


def _run(setup, **kw):
    cfg, data, traces, adj, plan, streams = setup
    return F.run_network_aware(cfg, data, traces, adj, plan,
                               streams=streams, engine="scan", **kw)


def _assert_hist_bitwise(ha, hb):
    assert ha["agg_round"] == hb["agg_round"]
    assert ha["test_acc"] == hb["test_acc"]
    assert ha["test_loss"] == hb["test_loss"]
    for a, b in zip(ha["device_loss"], hb["device_loss"]):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.asarray(ha["H_agg"]),
                                  np.asarray(hb["H_agg"]))


def test_chunked_checkpoint_matches_monolithic_bitwise(tmp_path):
    setup = _setup()
    mono = _run(setup)
    ck = os.path.join(tmp_path, "ck.msgpack")
    chunked = _run(setup, checkpoint_path=ck, checkpoint_every=1)
    _assert_hist_bitwise(mono, chunked)
    assert "stopped_at" not in chunked
    assert os.path.exists(ck)


def test_resume_mid_horizon_bitwise(tmp_path):
    setup = _setup()
    full = _run(setup)
    ck = os.path.join(tmp_path, "ck.msgpack")
    part = _run(setup, checkpoint_path=ck, stop_after=8)
    assert part["stopped_at"] == 8
    assert len(part["test_acc"]) == 2            # 2 of 3 windows ran
    resumed = _run(setup, resume=ck)
    _assert_hist_bitwise(full, resumed)


def test_resume_faulted_run_bitwise(tmp_path):
    setup = _setup()
    fs = fl.FaultSchedule(12, 6, 4, [
        fl.FaultEvent(3, "corrupt", 0, float("nan")),
        fl.FaultEvent(7, "drop", 1),
        fl.FaultEvent(5, "crash", 2)])
    kw = dict(faults=fs, guard=True, quorum=0.2)
    full = _run(setup, **kw)
    ck = os.path.join(tmp_path, "ck.msgpack")
    _run(setup, checkpoint_path=ck, stop_after=4, **kw)
    resumed = _run(setup, resume=ck, **kw)
    _assert_hist_bitwise(full, resumed)
    assert resumed["agg_survivors"] == full["agg_survivors"]
    assert resumed["agg_quorum_ok"] == full["agg_quorum_ok"]


def test_resume_rejects_mismatched_run_config(tmp_path):
    setup = _setup()
    ck = os.path.join(tmp_path, "ck.msgpack")
    _run(setup, checkpoint_path=ck, stop_after=4)
    cfg, data, traces, adj, plan, streams = setup
    other = F.FedConfig(n=6, T=12, tau=4, eta=0.01, model="mlp",
                        seed=0)
    with pytest.raises(ValueError, match="eta"):
        F.run_network_aware(other, data, traces, adj, plan,
                            streams=streams, engine="scan", resume=ck)


def test_resume_requires_scan_engine():
    cfg, data, traces, adj, plan, streams = _setup()
    with pytest.raises(ValueError, match="scan-engine"):
        F.run_network_aware(cfg, data, traces, adj, plan,
                            streams=streams, engine="sharded",
                            resume="/tmp/does-not-matter.msgpack")
