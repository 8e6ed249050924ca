"""fog-lint (repro.analysis), the runtime sanitizer harness, the
consolidated compile-event fan-out — and the oracle-pairing backfill
tests the analyzer demanded (every public ``*_edges``/``*_flat``
function cross-checked against its dense twin)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import all_rules, lint_paths, lint_sources, rules_by_name
from repro.core import estimator as est
from repro.core import federated as F
from repro.core import monitoring as mon
from repro.core import movement as mv
from repro.core import sanitize as sz
from repro.core import topology as topo
from repro.core.costs import (edge_costs_from_dense, synthetic_costs,
                              synthetic_edge_costs)
from repro.data import pipeline as pl

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src", "repro")
TESTS = os.path.join(REPO, "tests")


def run_rule(rule_name, sources, tests_sources=None):
    res = lint_sources(sources, rules_by_name([rule_name]),
                       tests_sources=tests_sources)
    return res


def names(res):
    return [(f.rule, f.line) for f in res.findings]


# ---------------------------------------------------------------------------
# rule fixtures: violating / clean / waived for every rule
# ---------------------------------------------------------------------------


class TestDenseMaterialization:
    def test_violating(self):
        src = ("import numpy as np\n"
               "def f(n):\n"
               "    A = np.zeros((n, n), bool)\n"
               "    B = np.outer(np.ones(n), np.ones(n))\n"
               "    return A, B\n")
        res = run_rule("dense-materialization", {"core/newmod.py": src})
        assert [line for _, line in names(res)] == [3, 4]

    def test_dense_view_and_plan_s(self):
        src = ("def f(sched, plan, t):\n"
               "    a = sched.adj_at(t)\n"
               "    return a, plan.s\n")
        res = run_rule("dense-materialization", {"core/newmod.py": src})
        assert [line for _, line in names(res)] == [2, 3]

    def test_broadcast_outer(self):
        src = ("def f(a, b):\n"
               "    return a[:, None] * b[None, :]\n")
        res = run_rule("dense-materialization", {"core/newmod.py": src})
        assert len(res.findings) == 1

    def test_clean(self):
        src = ("import numpy as np\n"
               "def f(n, k):\n"
               "    w = np.zeros((n, k))\n"       # non-square: fine
               "    e = np.zeros(n * 4)\n"
               "    return w, e\n")
        res = run_rule("dense-materialization", {"core/newmod.py": src})
        assert res.ok

    def test_designated_module_skipped(self):
        src = "import numpy as np\nA = np.zeros((n, n))\n"
        res = run_rule("dense-materialization", {"core/schedule.py": src})
        assert res.ok

    def test_waived(self):
        src = ("import numpy as np\n"
               "def f(n):\n"
               "    # foglint: disable=dense-materialization -- small-n oracle\n"
               "    return np.zeros((n, n))\n")
        res = run_rule("dense-materialization", {"core/newmod.py": src})
        assert res.ok and len(res.waived) == 1


class TestNanUnsafeMasking:
    def test_violating(self):
        src = ("def agg(mask, grads):\n"
               "    return mask * grads\n")
        res = run_rule("nan-unsafe-masking", {"core/faults.py": src})
        assert names(res) == [("nan-unsafe-masking", 2)]

    def test_clean_where_and_mask_times_mask(self):
        src = ("import jax.numpy as jnp\n"
               "def agg(mask, ok_flag, grads):\n"
               "    m = mask * ok_flag\n"          # mask·mask: finite
               "    return jnp.where(m > 0, grads, 0.0)\n")
        res = run_rule("nan-unsafe-masking", {"core/faults.py": src})
        assert res.ok

    def test_out_of_scope_module_ignored(self):
        src = "def f(mask, grads):\n    return mask * grads\n"
        res = run_rule("nan-unsafe-masking", {"data/other.py": src})
        assert res.ok

    def test_waived(self):
        src = ("def inject(params, cor):\n"
               "    # foglint: disable=nan-unsafe-masking -- injection, not a guard\n"
               "    return params * cor\n")
        res = run_rule("nan-unsafe-masking", {"core/faults.py": src})
        assert res.ok and len(res.waived) == 1


class TestRecompileHazard:
    def test_jit_in_loop(self):
        src = ("import jax\n"
               "def run(xs):\n"
               "    for x in xs:\n"
               "        y = jax.jit(lambda v: v + 1)(x)\n"
               "    return y\n")
        res = run_rule("recompile-hazard", {"core/newmod.py": src})
        assert names(res) == [("recompile-hazard", 4)]

    def test_bad_static_args(self):
        src = ("import jax\n"
               "f = jax.jit(g, static_argnums=[0])\n"
               "h = jax.jit(g, static_argnums=(1.5,))\n")
        res = run_rule("recompile-hazard", {"core/newmod.py": src})
        assert [line for _, line in names(res)] == [2, 3]

    def test_cached_builder_mutable_default(self):
        src = ("import functools\n"
               "@functools.lru_cache(maxsize=8)\n"
               "def _my_program(eta, opts=[]):\n"
               "    pass\n"
               "@functools.lru_cache(maxsize=8)\n"
               "def _other_program(eta, **kw):\n"
               "    pass\n")
        res = run_rule("recompile-hazard", {"core/newmod.py": src})
        assert len(res.findings) == 2

    def test_clean(self):
        src = ("import jax, functools\n"
               "step = jax.jit(lambda v: v + 1)\n"
               "@functools.lru_cache(maxsize=8)\n"
               "def _my_program(eta, use_faults=False):\n"
               "    return jax.jit(lambda v: v * eta)\n"
               "def run(xs):\n"
               "    for x in xs:\n"
               "        y = step(x)\n"
               "    return y\n")
        res = run_rule("recompile-hazard", {"core/newmod.py": src})
        assert res.ok

    def test_waived(self):
        src = ("import jax\n"
               "def run(xs):\n"
               "    for x in xs:\n"
               "        # foglint: disable=recompile-hazard -- one-off tool\n"
               "        y = jax.jit(lambda v: v + 1)(x)\n"
               "    return y\n")
        res = run_rule("recompile-hazard", {"core/newmod.py": src})
        assert res.ok and len(res.waived) == 1


class TestHostSyncInHotPath:
    def test_scan_body_sync(self):
        src = ("import jax\n"
               "def body(c, x):\n"
               "    v = float(x)\n"
               "    w = x.item()\n"
               "    return c, v + w\n"
               "def run(xs):\n"
               "    return jax.lax.scan(body, 0.0, xs)\n")
        res = run_rule("host-sync-in-hot-path", {"core/newmod.py": src})
        assert [line for _, line in names(res)] == [3, 4]

    def test_builder_nested_def_is_hot(self):
        src = ("import numpy as np\n"
               "def _bucket_program(eta):\n"
               "    def train(W, xs):\n"
               "        return np.asarray(W)\n"
               "    return train\n")
        res = run_rule("host-sync-in-hot-path", {"core/newmod.py": src})
        assert len(res.findings) == 1

    def test_shape_math_allowed(self):
        src = ("import jax\n"
               "import numpy as np\n"
               "def body(c, x):\n"
               "    k = int(np.prod(x.shape))\n"   # static metadata
               "    return c + k, x\n"
               "def run(xs):\n"
               "    return jax.lax.scan(body, 0.0, xs)\n")
        res = run_rule("host-sync-in-hot-path", {"core/newmod.py": src})
        assert res.ok

    def test_cold_function_ignored(self):
        src = ("def stage(xs):\n"
               "    return float(xs)\n")
        res = run_rule("host-sync-in-hot-path", {"core/newmod.py": src})
        assert res.ok

    def test_waived(self):
        src = ("import jax\n"
               "def body(c, x):\n"
               "    # foglint: disable=host-sync-in-hot-path -- debug hook\n"
               "    v = float(x)\n"
               "    return c, v\n"
               "def run(xs):\n"
               "    return jax.lax.scan(body, 0.0, xs)\n")
        res = run_rule("host-sync-in-hot-path", {"core/newmod.py": src})
        assert res.ok and len(res.waived) == 1


class TestRngStreamDiscipline:
    def test_violating(self):
        src = ("import numpy as np\n"
               "import jax\n"
               "def make(n):\n"
               "    r1 = np.random.default_rng()\n"
               "    r2 = np.random.default_rng(42)\n"
               "    x = np.random.rand(n)\n"
               "    k = jax.random.PRNGKey(0)\n"
               "    return r1, r2, x, k\n")
        res = run_rule("rng-stream-discipline", {"core/topology.py": src})
        assert [line for _, line in names(res)] == [4, 5, 6, 7]

    def test_clean_derived(self):
        src = ("import numpy as np\n"
               "import jax\n"
               "def make(seed, cfg):\n"
               "    r = np.random.default_rng(seed + 7919)\n"
               "    k = jax.random.PRNGKey(cfg.seed)\n"
               "    return r, k\n")
        res = run_rule("rng-stream-discipline", {"core/faults.py": src})
        assert res.ok

    def test_out_of_scope_module_ignored(self):
        src = "import numpy as np\nr = np.random.default_rng()\n"
        res = run_rule("rng-stream-discipline", {"core/engine.py": src})
        assert res.ok

    def test_waived(self):
        src = ("import numpy as np\n"
               "def make(rng=None):\n"
               "    # foglint: disable=rng-stream-discipline -- documented fixed default\n"
               "    return rng or np.random.default_rng(0)\n")
        res = run_rule("rng-stream-discipline", {"data/synthetic.py": src})
        assert res.ok and len(res.waived) == 1


class TestOraclePairing:
    SRC = ("def solve_edges(a):\n"
           "    return a\n"
           "def _private_edges(a):\n"
           "    return a\n"
           "def stage_flat(a):\n"
           "    return a\n")

    def test_violating(self):
        res = run_rule("oracle-pairing", {"core/newmod.py": self.SRC},
                       tests_sources={"test_x.py": "def test_nothing(): pass"})
        assert [line for _, line in names(res)] == [1, 5]

    def test_covered_clean(self):
        tests = {"test_x.py": "from m import solve_edges, stage_flat"}
        res = run_rule("oracle-pairing", {"core/newmod.py": self.SRC},
                       tests_sources=tests)
        assert res.ok

    def test_no_tests_tree_skips(self):
        res = run_rule("oracle-pairing", {"core/newmod.py": self.SRC})
        assert res.ok

    def test_waived(self):
        src = ("# foglint: disable=oracle-pairing -- thin re-export\n"
               "def solve_edges(a):\n"
               "    return a\n")
        res = run_rule("oracle-pairing", {"core/newmod.py": src},
                       tests_sources={"test_x.py": "x = 1"})
        assert res.ok and len(res.waived) == 1

    def test_tier_and_hierarchical_twins_require_flat_oracle(self):
        src = ("def aggregate_tier(a):\n"
               "    return a\n"
               "def run_rounds_hierarchical(a):\n"
               "    return a\n")
        res = run_rule("oracle-pairing", {"core/hier.py": src},
                       tests_sources={"test_x.py": "def test(): pass"})
        assert [line for _, line in names(res)] == [1, 3]
        tests = {"test_h.py": "from repro.core.engine import "
                              "aggregate_tier\n"
                              "run_rounds_hierarchical(...)"}
        res = run_rule("oracle-pairing", {"core/hier.py": src},
                       tests_sources=tests)
        assert res.ok


# ---------------------------------------------------------------------------
# waiver machinery
# ---------------------------------------------------------------------------


class TestWaivers:
    def test_missing_justification_is_a_finding_and_waives_nothing(self):
        src = ("import numpy as np\n"
               "def f(n):\n"
               "    # foglint: disable=dense-materialization\n"
               "    return np.zeros((n, n))\n")
        res = run_rule("dense-materialization", {"core/newmod.py": src})
        rules = [f.rule for f in res.findings]
        assert "waiver-justification" in rules
        assert "dense-materialization" in rules
        assert not res.waived

    def test_file_level_waiver(self):
        src = ("# foglint: disable-file=dense-materialization -- legacy dense module\n"
               "import numpy as np\n"
               "def f(n):\n"
               "    return np.zeros((n, n))\n"
               "def g(n):\n"
               "    return np.ones((n, n))\n")
        res = run_rule("dense-materialization", {"core/newmod.py": src})
        assert res.ok and len(res.waived) == 2

    def test_waiver_names_must_match_rule(self):
        src = ("import numpy as np\n"
               "def f(n):\n"
               "    # foglint: disable=nan-unsafe-masking -- wrong rule name\n"
               "    return np.zeros((n, n))\n")
        res = run_rule("dense-materialization", {"core/newmod.py": src})
        assert [f.rule for f in res.findings] == ["dense-materialization"]

    def test_unknown_rule_selection_raises(self):
        with pytest.raises(ValueError, match="unknown rule"):
            rules_by_name(["no-such-rule"])


# ---------------------------------------------------------------------------
# self-check: the repo lints clean, through the API and the CLI
# ---------------------------------------------------------------------------


class TestSelfCheck:
    def test_repo_lints_clean(self):
        res = lint_paths([SRC], all_rules(), tests_dir=TESTS)
        assert res.ok, "\n".join(f.format() for f in res.findings)
        # the waiver set is intentional and justified — growth here
        # should be deliberate, not drive-by
        assert len(res.waivers) <= 16
        assert all(w.justification for w in res.waivers)

    def test_cli_exits_zero_on_repo(self):
        env = dict(os.environ,
                   PYTHONPATH=os.path.join(REPO, "src")
                   + os.pathsep + os.environ.get("PYTHONPATH", ""))
        out = subprocess.run(
            [sys.executable, "-m", "repro.analysis"], cwd=REPO, env=env,
            capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "0 finding(s)" in out.stdout

    def test_cli_list_waivers(self):
        env = dict(os.environ,
                   PYTHONPATH=os.path.join(REPO, "src")
                   + os.pathsep + os.environ.get("PYTHONPATH", ""))
        out = subprocess.run(
            [sys.executable, "-m", "repro.analysis", "--list-waivers"],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=300)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "0 missing justification" in out.stdout

    def test_cli_nonzero_on_violation(self, tmp_path):
        bad = tmp_path / "core"
        bad.mkdir()
        (bad / "newmod.py").write_text(
            "import numpy as np\nA = np.zeros((n, n))\n")
        env = dict(os.environ,
                   PYTHONPATH=os.path.join(REPO, "src")
                   + os.pathsep + os.environ.get("PYTHONPATH", ""))
        out = subprocess.run(
            [sys.executable, "-m", "repro.analysis", str(tmp_path)],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=300)
        assert out.returncode == 1
        assert "dense-materialization" in out.stdout


# ---------------------------------------------------------------------------
# monitoring fan-out (the consolidated backend_compile registration)
# ---------------------------------------------------------------------------


class TestMonitoringFanout:
    def test_subscribers_share_one_registration(self):
        if not mon.listener_installed():
            pytest.skip("jax.monitoring unavailable")
        a, b = [], []
        mon.subscribe_compile(a.append)
        mon.subscribe_compile(b.append)
        try:
            before = mon.compile_events()
            jax.jit(lambda x: x * 3 + 17)(
                jnp.arange(23.0)).block_until_ready()
            delta = mon.compile_events() - before
            assert delta > 0
            assert len(a) == len(b) == delta
        finally:
            mon.unsubscribe_compile(a.append)
            mon.unsubscribe_compile(b.append)

    def test_costmodel_and_bench_counter_agree(self):
        from repro.core import costmodel as cm
        sys.path.insert(0, REPO)
        try:
            from benchmarks import run as br
        finally:
            sys.path.pop(0)
        cm.install_listener()
        n_subs = len(mon._SUBSCRIBERS)
        cm.install_listener()   # idempotent: no second subscription
        assert len(mon._SUBSCRIBERS) == n_subs
        before_model = cm.MODEL.compile_events
        before_count = br.compile_count()
        assert before_count == mon.compile_events()
        jax.jit(lambda x: x - 29)(jnp.arange(31.0)).block_until_ready()
        delta = mon.compile_events() - before_count
        if mon.listener_installed():
            assert delta > 0
            assert cm.MODEL.compile_events - before_model == delta
        assert br.compile_count() == mon.compile_events()

    def test_broken_subscriber_does_not_starve_others(self):
        if not mon.listener_installed():
            pytest.skip("jax.monitoring unavailable")
        def boom(_):
            raise RuntimeError("subscriber bug")
        good = []
        mon.subscribe_compile(boom)
        mon.subscribe_compile(good.append)
        try:
            jax.jit(lambda x: x / 7)(jnp.arange(37.0)).block_until_ready()
            assert good
        finally:
            mon.unsubscribe_compile(boom)
            mon.unsubscribe_compile(good.append)


# ---------------------------------------------------------------------------
# runtime sanitizer harness
# ---------------------------------------------------------------------------


class TestSanitize:
    def test_watchdog_raises_on_warm_compile(self):
        if not mon.listener_installed():
            pytest.skip("jax.monitoring unavailable")
        with pytest.raises(sz.RecompileError):
            with sz.sanitized(sz.SanitizeConfig(expect_warm=True,
                                                debug_nans=False)):
                jax.jit(lambda x: x + 41)(
                    jnp.arange(43.0)).block_until_ready()

    def test_config_saved_and_restored(self):
        before = jax.config.jax_debug_nans
        with sz.sanitized(True):
            assert jax.config.jax_debug_nans
            assert sz.active() is not None
        assert jax.config.jax_debug_nans == before
        assert sz.active() is None

    def test_false_is_a_noop(self):
        with sz.sanitized(False) as cfg:
            assert cfg is None and sz.active() is None

    def test_hot_loop_guard_inert_outside_sanitized(self):
        with sz.hot_loop_guard():
            np.asarray(jnp.arange(3.0))  # implicit transfer: allowed

    def test_debug_nans_catches_engine_nan(self):
        with sz.sanitized(sz.SanitizeConfig(transfer_guard=False)):
            with pytest.raises(FloatingPointError):
                jnp.log(jnp.zeros(3) - 1.0).block_until_ready()

    def test_engine_history_bitwise_under_sanitize(self, small_images):
        cfg = F.FedConfig(n=5, T=6, tau=3, model="mlp", seed=3)
        traces = synthetic_costs(cfg.n, cfg.T, np.random.default_rng(1))
        plan = mv.no_movement_plan(cfg.T, cfg.n)
        h0 = F.run_network_aware(cfg, small_images, traces, None, plan)
        h1 = F.run_network_aware(cfg, small_images, traces, None, plan,
                                 sanitize=True)
        for k in ("test_acc", "test_loss", "device_loss"):
            assert np.array_equal(np.asarray(h0[k]), np.asarray(h1[k]))
        # warm sanitized re-run must not compile anything
        warm = sz.SanitizeConfig(expect_warm=True)
        h2 = F.run_network_aware(cfg, small_images, traces, None, plan,
                                 sanitize=warm)
        assert np.array_equal(np.asarray(h1["test_acc"]),
                              np.asarray(h2["test_acc"]))
        if mon.listener_installed():
            assert getattr(warm, "last_compiles", 0) == 0

    def test_bad_sanitize_value_rejected(self):
        with pytest.raises(TypeError, match="SanitizeConfig"):
            sz.SanitizeConfig.coerce("yes")


# ---------------------------------------------------------------------------
# regression tests for the two fixed violations
# ---------------------------------------------------------------------------


def _dense_prediction_accuracy(predicted, truth):
    """The pre-fix O(T·n²) formula, kept verbatim as the oracle."""
    support = np.zeros((truth.n, truth.n), bool)
    for t in range(truth.T):
        support |= np.asarray(truth.adj_at(t), bool)
        support |= np.asarray(predicted.adj_at(t), bool)
    agree = total = 0.0
    for t in range(truth.T):
        p = np.asarray(predicted.adj_at(t), bool)[support]
        q = np.asarray(truth.adj_at(t), bool)[support]
        agree += float((p == q).sum())
        total += float(support.sum())
    act_acc = float((predicted.activity() == truth.activity()).mean())
    return {"link_accuracy": agree / total if total else 1.0,
            "activity_accuracy": act_acc}


class TestScheduleAccuracyFix:
    def test_bitwise_vs_dense_formula_dense_storage(self):
        n, T = 24, 12
        rng = np.random.default_rng(5)
        adj = topo.random_graph(n, 0.4, rng)
        truth = topo.churn_schedule(adj, T, 0.1, 0.3,
                                    np.random.default_rng(6))
        predicted = est.predict_schedule(truth, L=3)
        got = est.schedule_prediction_accuracy(predicted, truth)
        want = _dense_prediction_accuracy(predicted, truth)
        assert got == want  # exact, not approx

    def test_bitwise_vs_dense_formula_edgelist_storage(self):
        n, T = 32, 10
        rng = np.random.default_rng(7)
        src, dst = topo.random_sparse_edges(n, 4, rng)
        truth = topo.link_flap_schedule_edges(
            n, src, dst, T, np.random.default_rng(8), p_down=0.2,
            p_up=0.5)
        predicted = est.predict_schedule(truth, L=2)
        got = est.schedule_prediction_accuracy(predicted, truth)
        want = _dense_prediction_accuracy(predicted, truth)
        assert got == want

    def test_scores_past_dense_view_guard(self):
        from repro.core.schedule import DENSE_VIEW_MAX_N
        n = DENSE_VIEW_MAX_N + 64
        T = 5
        src, dst = topo.ring_lattice_edges(n, 4)
        truth = topo.churn_schedule_edges(n, src, dst, T, 0.05, 0.2,
                                          np.random.default_rng(9))
        predicted = est.predict_schedule(truth, L=2)
        # the old dense formula cannot even look at this schedule
        with pytest.raises(Exception):
            truth.adj_at(0)
        out = est.schedule_prediction_accuracy(predicted, truth)
        assert 0.0 <= out["link_accuracy"] <= 1.0
        assert 0.0 <= out["activity_accuracy"] <= 1.0

    def test_empty_support(self):
        from repro.core.schedule import NetworkSchedule
        empty = NetworkSchedule.constant(np.zeros((4, 4), bool), 3)
        out = est.schedule_prediction_accuracy(empty, empty)
        assert out["link_accuracy"] == 1.0


class TestRunFederatedAdjFix:
    def test_history_identical_without_dense_default(self, small_images):
        cfg = F.FedConfig(n=5, T=6, tau=3, model="mlp", seed=1)
        h_new = F.run_federated(cfg, small_images)
        h_old = F.run_federated(cfg, small_images,
                                adj=np.ones((cfg.n, cfg.n), bool))
        assert h_new.keys() == h_old.keys()
        for k in ("test_acc", "test_loss", "device_loss"):
            assert np.array_equal(np.asarray(h_new[k]),
                                  np.asarray(h_old[k]))


# ---------------------------------------------------------------------------
# oracle-pairing backfill: the 8 uncovered *_edges/*_flat functions
# ---------------------------------------------------------------------------


class TestOraclePairingBackfill:
    def test_ring_lattice_edges_matches_watts_strogatz_beta0(self):
        for n, k in ((16, 4), (9, 3), (30, 6)):
            src, dst = topo.ring_lattice_edges(n, k)
            dense = np.zeros((n, n), bool)
            dense[src, dst] = True
            want = topo.watts_strogatz(n, k, 0.0,
                                       np.random.default_rng(0))
            np.testing.assert_array_equal(dense, want)

    def test_counts_flat_matches_counts(self, small_images):
        _, y_tr, _, _ = small_images
        streams = pl.poisson_streams(10, 6, y_tr,
                                     rng=np.random.default_rng(3),
                                     mean_per_round=2.5)
        flat = pl.flat_from_streams(streams)
        np.testing.assert_array_equal(pl.counts(streams),
                                      pl.counts_flat(flat))

    def test_streams_from_flat_roundtrip(self, small_images):
        _, y_tr, _, _ = small_images
        streams = pl.poisson_streams(8, 5, y_tr,
                                     rng=np.random.default_rng(4),
                                     mean_per_round=2.0)
        back = pl.streams_from_flat(pl.flat_from_streams(streams))
        assert (back.n, back.T) == (streams.n, streams.T)
        for t in range(streams.T):
            for i in range(streams.n):
                np.testing.assert_array_equal(
                    back.collected[t][i], streams.collected[t][i])

    @staticmethod
    def _bangbang_setup(y_tr, n=12, T=6):
        rng = np.random.default_rng(0)
        src, dst = topo.random_sparse_edges(n, 4, rng)
        sched = topo.churn_schedule_edges(n, src, dst, T, 0.1, 0.3,
                                          np.random.default_rng(2))
        etr = synthetic_edge_costs(n, T, src, dst,
                                   np.random.default_rng(1))
        plan = mv.realize_plan(mv.greedy_linear(etr, sched), sched)
        streams = pl.poisson_streams(n, T, y_tr,
                                     rng=np.random.default_rng(3),
                                     mean_per_round=2.0)
        return plan, streams

    def test_apply_movement_flat_matches_listwise(self, small_images):
        _, y_tr, _, _ = small_images
        plan, streams = self._bangbang_setup(y_tr)
        proc_lists = pl.apply_movement(streams, plan,
                                       np.random.default_rng(5))
        proc_flat = pl.apply_movement_flat(pl.flat_from_streams(streams),
                                           plan,
                                           np.random.default_rng(5))
        back = pl.streams_from_flat(proc_flat)
        for t in range(streams.T):
            for i in range(streams.n):
                np.testing.assert_array_equal(
                    np.sort(back.collected[t][i]),
                    np.sort(proc_lists[t][i]))

    def test_stage_rounds_flat_matches_listwise(self, small_images):
        _, y_tr, _, _ = small_images
        plan, streams = self._bangbang_setup(y_tr)
        proc_lists = pl.apply_movement(streams, plan,
                                       np.random.default_rng(5))
        proc_flat = pl.apply_movement_flat(pl.flat_from_streams(streams),
                                           plan,
                                           np.random.default_rng(5))
        P = max(len(ix) for row in proc_lists for ix in row) or 1
        idx_l, yb_l, w_l, c_l = pl.stage_rounds(proc_lists, y_tr, P)
        idx_f, yb_f, w_f, c_f = pl.stage_rounds(proc_flat, y_tr, P)
        np.testing.assert_array_equal(c_l, c_f)
        np.testing.assert_array_equal(w_l.sum(-1), w_f.sum(-1))
        T, n = c_l.shape
        for t in range(T):
            for i in range(n):
                kl = int(c_l[t, i])
                np.testing.assert_array_equal(
                    np.sort(idx_l[t, i, :kl]), np.sort(idx_f[t, i, :kl]))
                np.testing.assert_array_equal(
                    np.sort(yb_l[t, i, :kl]), np.sort(yb_f[t, i, :kl]))

    def test_greedy_linear_edges_matches_dense(self):
        n, T = 16, 6
        rng = np.random.default_rng(11)
        adj = topo.random_graph(n, 0.5, rng)
        traces = synthetic_costs(n, T, np.random.default_rng(12))
        src, dst = np.nonzero(adj)
        etr = edge_costs_from_dense(traces, src, dst)
        plan_d = mv.greedy_linear(traces, adj, backend="numpy")
        plan_e = mv.greedy_linear_edges(etr, adj)
        np.testing.assert_array_equal(plan_e.r, plan_d.r)
        np.testing.assert_array_equal(plan_e.s, plan_d.s)

    def test_aggregate_edges_matches_dense_aggregate(self):
        from repro.core.engine import aggregate, aggregate_edges
        rng = np.random.default_rng(13)
        n = 9
        W = {"w": jnp.asarray(rng.standard_normal((n, 4, 3)),
                              jnp.float32),
             "b": jnp.asarray(rng.standard_normal((n, 5)), jnp.float32)}
        H = jnp.asarray(rng.random(n), jnp.float32)
        ids = np.array([1, 3, 4, 7])
        mask = np.zeros(n, np.float32)
        mask[ids] = 1.0
        prev = {"w": jnp.zeros((4, 3), jnp.float32),
                "b": jnp.zeros(5, jnp.float32)}
        want = aggregate(W, H, jnp.asarray(mask), prev)
        got = aggregate_edges(W, H, ids, prev)
        for k in W:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(want[k]), rtol=2e-6,
                                       atol=1e-7)

    def test_offload_greedy_edges_matches_ref_emission(self):
        from repro.kernels import ops, ref
        from repro.kernels.offload_greedy import offload_greedy_edges
        rng = np.random.default_rng(14)
        T, n = 3, 128
        c_link = jnp.asarray(rng.random((T, n, n)), jnp.float32)
        c_next = jnp.asarray(rng.random((T, n)), jnp.float32)
        c_node = jnp.asarray(rng.random((T, n)), jnp.float32)
        f_err = jnp.asarray(rng.random((T, n)), jnp.float32)
        adj = jnp.asarray(rng.random((T, n, n)) < 0.3)
        got = offload_greedy_edges(c_link, c_next, c_node, f_err, adj,
                                   interpret=True)
        want = ops.greedy_edges_batched(c_link, c_next, c_node, f_err,
                                        adj, use_pallas=False)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        del ref
