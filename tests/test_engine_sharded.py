"""Device-sharded engine vs the scan engine and the legacy oracle.

In-process tests run on whatever devices exist (a 1-device "data" mesh
must reproduce the scan engine exactly up to compiler scheduling); the
multi-device equivalence — n padded across 8 forced host devices,
aggregation as a cross-shard psum, churn masking — runs in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8,
the same mechanism as tests/test_distributed.py (device count locks at
first jax init). CI runs this file again under a forced 8-device
environment."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np

from repro.core import engine as eng
from repro.core import federated as F
from repro.core import movement as mv
from repro.core.costs import synthetic_costs
from repro.core.topology import fully_connected
from repro.data import pipeline as pl
from repro.data.synthetic import make_image_dataset

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _setup(n=6, T=12, tau=4, p_exit=0.0, p_entry=0.0, seed=0):
    data = make_image_dataset(n_train=1200, n_test=400, seed=0)
    cfg = F.FedConfig(n=n, T=T, tau=tau, eta=0.05, model="mlp", seed=seed,
                      p_exit=p_exit, p_entry=p_entry)
    rng = np.random.default_rng(seed)
    traces = synthetic_costs(n, T, rng)
    adj = fully_connected(n)
    streams = pl.poisson_streams(n, T, data[1], rng=rng)
    plan = mv.greedy_linear(traces, adj)
    activity = F.churn_activity(cfg, rng) if (p_exit or p_entry) else None
    return cfg, data, traces, adj, plan, streams, activity


def _run(engine, **kw):
    cfg, data, traces, adj, plan, streams, activity = _setup(**kw)
    return F.run_network_aware(cfg, data, traces, adj, plan,
                               streams=streams, activity=activity,
                               engine=engine)


def _assert_equivalent(h_ref, h_sharded):
    assert h_ref["agg_round"] == h_sharded["agg_round"]
    np.testing.assert_allclose(h_sharded["test_acc"], h_ref["test_acc"],
                               atol=1e-2)
    np.testing.assert_allclose(h_sharded["test_loss"], h_ref["test_loss"],
                               rtol=2e-3, atol=1e-4)
    np.testing.assert_allclose(np.stack(h_sharded["device_loss"]),
                               np.stack(h_ref["device_loss"]),
                               rtol=2e-3, atol=1e-4)
    np.testing.assert_allclose(np.stack(h_sharded["H_agg"]),
                               np.stack(h_ref["H_agg"]), atol=1e-4)


def test_sharded_matches_scan_in_process():
    _assert_equivalent(_run("scan"), _run("sharded"))


def test_sharded_matches_legacy_offset_tau():
    # T not a multiple of tau + n not a multiple of the mesh extent
    _assert_equivalent(_run("legacy", n=5, T=10, tau=3),
                       _run("sharded", n=5, T=10, tau=3))


def test_sharded_history_contract_keys():
    h = _run("sharded")
    for key in ("round", "device_loss", "test_acc", "test_loss",
                "agg_round", "active", "processed_counts", "sim_before",
                "sim_after", "H_agg"):
        assert key in h, key
    assert len(h["round"]) == len(h["device_loss"]) == 12
    assert np.stack(h["device_loss"]).shape[1] == 6     # phantoms sliced


def test_async_evaluator_streams_and_matches_direct():
    import jax

    data = make_image_dataset(n_train=600, n_test=200, seed=0)
    params, apply_fn = eng.make_model("mlp", jax.random.PRNGKey(0))
    ev = eng.AsyncEvaluator(apply_fn, data[2], data[3])
    ev.submit(params)
    ev.submit(params)
    losses, accs = ev.collect()
    tl, ta = eng._eval_program(apply_fn)(
        params, eng._to_device_cached(data[2]),
        eng._to_device_cached(data[3]))
    assert losses == [float(tl)] * 2 and accs == [float(ta)] * 2
    assert ev.collect() == ([], [])                     # drained


def test_device_cache_evicts_lru_only():
    eng._DEVICE_CACHE.clear()
    arrays = [np.full((4,), i, np.float32)
              for i in range(eng._DEVICE_CACHE_CAP + 1)]
    first = arrays[0]
    eng._to_device_cached(first)
    for a in arrays[1:-1]:
        eng._to_device_cached(a)
    eng._to_device_cached(first)            # refresh: first is now MRU
    eng._to_device_cached(arrays[-1])       # evicts the LRU, not first
    keys = list(eng._DEVICE_CACHE)
    assert len(keys) == eng._DEVICE_CACHE_CAP
    assert any(k[0] == id(first) for k in keys)
    assert not any(k[0] == id(arrays[1]) for k in keys)
    eng._DEVICE_CACHE.clear()


def test_device_cache_pins_the_row_view_once_per_array():
    """The prestaged gather's (N, F) operand is keyed on the host array:
    every later call finds it, beside the image-shaped entry."""
    eng._DEVICE_CACHE.clear()
    x = np.arange(24, dtype=np.float32).reshape(4, 3, 2)
    rows = eng._to_device_cached(x, rows=True)
    assert eng._to_device_cached(x, rows=True) is rows
    np.testing.assert_array_equal(rows, x.reshape(4, 6))
    assert eng._to_device_cached(x).shape == (4, 3, 2)
    assert len(eng._DEVICE_CACHE) == 2
    eng._DEVICE_CACHE.clear()


def test_sharded_multi_device_equivalence():
    """8 forced host devices: sharded (n=6 padded to 8, then n=10 with
    2 fog devices per shard, plus churn) must match the scan engine and
    the legacy oracle within the standard tolerances."""
    code = """
        import json
        import numpy as np
        import jax
        assert jax.device_count() == 8, jax.device_count()
        from repro.core import federated as F
        from repro.core import movement as mv
        from repro.core.costs import synthetic_costs
        from repro.core.topology import fully_connected
        from repro.data import pipeline as pl
        from repro.data.synthetic import make_image_dataset

        def run(engine, n, T, tau, p_exit=0.0, p_entry=0.0, seed=0):
            data = make_image_dataset(n_train=1000, n_test=300, seed=0)
            cfg = F.FedConfig(n=n, T=T, tau=tau, eta=0.05, model="mlp",
                              seed=seed, p_exit=p_exit, p_entry=p_entry)
            rng = np.random.default_rng(seed)
            traces = synthetic_costs(n, T, rng)
            adj = fully_connected(n)
            streams = pl.poisson_streams(n, T, data[1], rng=rng)
            plan = mv.greedy_linear(traces, adj)
            activity = (F.churn_activity(cfg, rng)
                        if (p_exit or p_entry) else None)
            return F.run_network_aware(cfg, data, traces, adj, plan,
                                       streams=streams, activity=activity,
                                       engine=engine)

        out = {}
        for tag, kw in {"pad": dict(n=6, T=8, tau=4),
                        "multi": dict(n=10, T=9, tau=3),
                        "churn": dict(n=8, T=8, tau=4, p_exit=0.2,
                                      p_entry=0.15, seed=3)}.items():
            hs = run("sharded", **kw)
            for ref_name in ("scan", "legacy"):
                h = run(ref_name, **kw)
                out[f"{tag}/{ref_name}"] = {
                    "agg_match": h["agg_round"] == hs["agg_round"],
                    "acc": float(np.abs(np.array(h["test_acc"])
                                        - np.array(hs["test_acc"])).max()),
                    "loss": float(np.abs(np.array(h["test_loss"])
                                         - np.array(hs["test_loss"])).max()),
                    "H": float(np.abs(np.stack(h["H_agg"])
                                      - np.stack(hs["H_agg"])).max()),
                    "dl": float(np.abs(np.stack(h["device_loss"])
                                       - np.stack(hs["device_loss"])).max()),
                }
        print(json.dumps(out))
    """
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    d = json.loads(r.stdout.strip().splitlines()[-1])
    for tag, gaps in d.items():
        assert gaps["agg_match"], (tag, gaps)
        assert gaps["acc"] <= 1e-2, (tag, gaps)
        assert gaps["loss"] <= 1e-3, (tag, gaps)
        assert gaps["H"] <= 1e-4, (tag, gaps)
        assert gaps["dl"] <= 1e-3, (tag, gaps)
