"""Static-analysis tests (ISSUE 5 tentpole).

The contract under test (docs/static_analysis.md):

* the AST linter flags each framework invariant (H101 raw writes, H201
  unregistered env knobs, H301 unaccounted collectives, H302
  unregistered fault sites, H401 host syncs in chunk bodies, H501
  fault-swallowing broad excepts, H601 clock seeding) on embedded bad
  fixtures and stays silent on the good twins;
* ``# lint: allow <rule>(reason)`` suppresses exactly that rule on that
  line; the checked-in sources are clean against the baseline;
* ``scripts/lint_gate.py`` fails on any violation not in the baseline,
  reports fixed baseline entries as stale, and ``--update`` rewrites the
  baseline (same gate pattern as ``perf_gate.py``);
* the jaxpr/HLO program analyzer flags the three seeded SPMD hazards —
  an implicit unaccounted collective (J101), a weak-type recompile pair
  (J103), a failed donation (J104) — plus full gathers (J102) and silent
  promotion (J105), and reports ZERO diagnostics on the clean kmeans
  Lloyd step;
* the dispatch compile-path hook surfaces scalar-dtype cache churn as
  J103, honors warn/raise/off modes, and raise-mode errors propagate
  through the dispatch compile-fallback.
"""

import json
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

import heat_tpu as ht
from heat_tpu import analysis, telemetry
from heat_tpu.analysis import (
    AnalysisWarning,
    Diagnostic,
    ProgramLintError,
    analyze,
    diagnostics,
)
from heat_tpu.analysis.ast_lint import lint_file, lint_paths
from heat_tpu.analysis.program_lint import reset_dispatch_state
from heat_tpu.core import dispatch

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))

from lint_gate import run_gate  # noqa: E402

KNOBS = {"HEAT_TPU_REGISTERED"}
SITES = {"good.site", "kmeans.iter"}


def lint_src(src, rel="heat_tpu/somemod.py", knobs=KNOBS, sites=SITES):
    """Lint an embedded fixture without touching the filesystem."""
    return lint_file(
        "<fixture>", repo_root=REPO_ROOT, knobs=knobs, sites=sites,
        source=textwrap.dedent(src), rel_path=rel,
    )


def rules(violations):
    return [v.rule for v in violations]


# ----------------------------------------------------------------------
# AST rules on embedded fixtures
# ----------------------------------------------------------------------
class TestH101RawWrites:
    def test_write_mode_flags(self):
        v = lint_src("""
            def dump(path, doc):
                with open(path, "w") as f:
                    f.write(doc)
        """)
        assert rules(v) == ["H101"]
        assert v[0].line == 3

    def test_binary_and_append_modes_flag(self):
        v = lint_src("""
            f = open(p, "wb")
            g = open(p, mode="a")
        """)
        assert rules(v) == ["H101", "H101"]

    def test_read_mode_clean(self):
        assert lint_src("""
            with open(p) as f:
                f.read()
            with open(p, "rb") as f:
                f.read()
        """) == []

    def test_inside_atomic_write_clean(self):
        assert lint_src("""
            from heat_tpu.resilience.atomic import atomic_write
            with atomic_write(p, "w") as tmp:
                with open(tmp, "w") as f:
                    f.write(doc)
        """) == []

    def test_sanctioned_file_clean(self):
        assert lint_src(
            'f = open(p, "w")\n', rel="heat_tpu/resilience/atomic.py"
        ) == []


class TestH201EnvKnobs:
    def test_unregistered_get_flags(self):
        v = lint_src('import os\nx = os.environ.get("HEAT_TPU_TYPO", "1")\n')
        assert rules(v) == ["H201"]

    def test_getenv_and_subscript_flag(self):
        v = lint_src("""
            import os
            a = os.getenv("HEAT_TPU_NOPE")
            b = os.environ["HEAT_TPU_ALSO_NOPE"]
        """)
        assert rules(v) == ["H201", "H201"]

    def test_registered_and_foreign_names_clean(self):
        assert lint_src("""
            import os
            a = os.environ.get("HEAT_TPU_REGISTERED")
            b = os.environ.get("XLA_FLAGS", "")
            c = os.environ["PATH"]
        """) == []

    def test_real_registry_covers_sources(self):
        # every knob the shipped sources read is registered: the repo
        # lints clean under the real KNOBS table (see TestRepoIsClean)
        from heat_tpu.analysis.ast_lint import load_registered_knobs

        knobs = load_registered_knobs(REPO_ROOT)
        assert "HEAT_TPU_ANALYZE" in knobs and "HEAT_TPU_FUSION" in knobs
        from heat_tpu.core._env import KNOBS as table

        assert set(table) == knobs
        for name, (typ, default, doc) in table.items():
            assert name.startswith("HEAT_TPU_")
            assert typ in ("bool", "int", "float", "str", "path", "choice")
            assert isinstance(default, str) and isinstance(doc, str) and doc


class TestH301CommCollectives:
    COMM = "heat_tpu/parallel/comm.py"

    def test_unaccounted_collective_flags(self):
        v = lint_src("""
            import jax
            def psum(self, x, axis_name):
                return jax.lax.psum(x, axis_name)
        """, rel=self.COMM)
        assert rules(v) == ["H301"]

    def test_accounted_collective_clean(self):
        assert lint_src("""
            import jax
            def psum(self, x, axis_name):
                with self._account("psum", x, axis_name):
                    return jax.lax.psum(x, axis_name)
        """, rel=self.COMM) == []

    def test_other_files_exempt(self):
        assert lint_src(
            "import jax\ny = jax.lax.psum(x, 'd')\n", rel="heat_tpu/nn/foo.py"
        ) == []


class TestH302FaultSites:
    def test_unregistered_inject_flags(self):
        v = lint_src("""
            from heat_tpu.resilience.faults import inject
            inject("bad.site", step=1)
        """)
        assert rules(v) == ["H302"]
        assert "bad.site" in v[0].message

    def test_registered_inject_clean(self):
        assert lint_src("""
            from heat_tpu.resilience.faults import inject as _inject
            _inject("good.site")
        """) == []

    def test_fault_site_kwarg_and_default_flag(self):
        v = lint_src("""
            def save(path, fault_site="nope.write"):
                atomic_write(path, fault_site="also.nope")
        """)
        assert rules(v) == ["H302", "H302"]


class TestH401HostSyncInChunk:
    def test_item_in_chunk_body_flags(self):
        v = lint_src("""
            def fit(x, state):
                def step_chunk(state, n):
                    s = state[0].item()
                    return state
                return resumable_fit_loop(step_chunk, state, site="kmeans.iter")
        """)
        assert rules(v) == ["H401"]

    def test_device_get_and_asarray_flag(self):
        v = lint_src("""
            import jax
            import numpy as np
            def run_chunk(state, n):
                a = jax.device_get(state)
                b = np.asarray(state)
                return state
        """)
        assert rules(v) == ["H401", "H401"]

    def test_outside_chunk_clean(self):
        assert lint_src("""
            def fit(x):
                return float(x.sum().item())
        """) == []


class TestH501BroadExcept:
    def test_swallowing_handler_flags(self):
        v = lint_src("""
            try:
                state = restore(step)
            except Exception:
                state = None
        """)
        assert rules(v) == ["H501"]

    def test_bare_and_tuple_flag(self):
        v = lint_src("""
            try:
                go()
            except:
                pass
            try:
                go()
            except (ValueError, Exception):
                pass
        """)
        assert rules(v) == ["H501", "H501"]

    def test_reraising_handler_clean(self):
        assert lint_src("""
            try:
                commit()
            except BaseException:
                cleanup()
                raise
        """) == []

    def test_narrow_handler_clean(self):
        assert lint_src("""
            try:
                state = restore(step)
            except FileNotFoundError:
                state = None
        """) == []


class TestH601ClockSeeding:
    def test_clock_seed_flags(self):
        v = lint_src("""
            import time
            def seed(new_seed=None):
                if new_seed is None:
                    new_seed = int(time.time() * 1000) & 0x7FFFFFFF
                return new_seed
        """)
        assert rules(v) == ["H601"]
        assert "default_seed" in v[0].message

    def test_clock_outside_seeding_clean(self):
        assert lint_src("""
            import time
            def elapsed(t0):
                return time.time() - t0
        """) == []


class TestSuppressions:
    def test_matching_rule_suppressed(self):
        assert lint_src("""
            try:
                go()
            except Exception:  # lint: allow H501(optional import guard)
                pass
        """) == []

    def test_wrong_rule_id_not_suppressed(self):
        v = lint_src("""
            try:
                go()
            except Exception:  # lint: allow H101(not the right rule)
                pass
        """)
        assert rules(v) == ["H501"]


class TestRepoIsClean:
    def test_cli_exits_zero_against_baseline(self, capsys):
        from heat_tpu.analysis.__main__ import main

        assert main([os.path.join(REPO_ROOT, "heat_tpu")]) == 0

    def test_list_rules(self, capsys):
        from heat_tpu.analysis.__main__ import main

        assert main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rule in ("H101", "H201", "H301", "H302", "H401", "H501", "H601"):
            assert rule in out


# ----------------------------------------------------------------------
# baseline gate semantics (scripts/lint_gate.py)
# ----------------------------------------------------------------------
#: rule -> (file name inside the fixture tree, violating source)
BAD_FIXTURES = {
    "H101": ("mod.py", 'f = open(p, "w")\n'),
    "H201": ("mod.py", 'import os\nx = os.environ.get("HEAT_TPU_TYPO")\n'),
    "H301": ("parallel/comm.py",
             "import jax\n\ndef psum(x, n):\n    return jax.lax.psum(x, n)\n"),
    "H302": ("mod.py",
             'from heat_tpu.resilience.faults import inject\ninject("no.such.site")\n'),
    "H401": ("mod.py",
             "def run_chunk(state, n):\n    return state[0].item()\n"),
    "H501": ("mod.py", "try:\n    go()\nexcept Exception:\n    pass\n"),
    "H601": ("mod.py", "import time\n\ndef seed():\n    return int(time.time())\n"),
}


class TestLintGate:
    def _fixture_dir(self, tmp_path, name="mod.py", src=BAD_FIXTURES["H501"][1]):
        d = tmp_path / "src"
        f = d / name
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(src)
        return d

    def test_new_violation_fails_then_update_accepts(self, tmp_path):
        d = self._fixture_dir(tmp_path)
        baseline = tmp_path / "baseline.json"
        res = run_gate(paths=[str(d)], baseline_path=str(baseline), quiet=True)
        assert res["new_count"] == 1 and res["new"][0]["rule"] == "H501"

        # --update accepts the current set; the rerun gates clean
        run_gate(paths=[str(d)], baseline_path=str(baseline), update=True,
                 quiet=True)
        assert json.load(open(baseline))["violations"][0]["rule"] == "H501"
        res = run_gate(paths=[str(d)], baseline_path=str(baseline), quiet=True)
        assert res["new_count"] == 0 and res["fixed_count"] == 0

    def test_fixed_violation_reported_stale(self, tmp_path):
        d = self._fixture_dir(tmp_path)
        baseline = tmp_path / "baseline.json"
        run_gate(paths=[str(d)], baseline_path=str(baseline), update=True,
                 quiet=True)
        (d / "mod.py").write_text("try:\n    go()\nexcept ValueError:\n    pass\n")
        res = run_gate(paths=[str(d)], baseline_path=str(baseline), quiet=True)
        assert res["new_count"] == 0
        assert res["fixed_count"] == 1 and res["fixed"][0]["rule"] == "H501"

    @pytest.mark.parametrize("rule", sorted(BAD_FIXTURES))
    def test_each_rule_family_gates(self, tmp_path, rule):
        name, src = BAD_FIXTURES[rule]
        d = self._fixture_dir(tmp_path, name=name, src=src)
        res = run_gate(paths=[str(d)], baseline_path=str(tmp_path / "b.json"),
                       quiet=True)
        assert res["new_count"] == 1 and res["new"][0]["rule"] == rule

    def test_gate_script_nonzero_exit_prints_location(self, tmp_path):
        d = self._fixture_dir(tmp_path)
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "scripts", "lint_gate.py"),
             "--paths", str(d), "--baseline", str(tmp_path / "b.json")],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 1
        assert "H501" in proc.stdout and "mod.py:3" in proc.stdout


# ----------------------------------------------------------------------
# jaxpr/HLO program analyzer
# ----------------------------------------------------------------------
@pytest.fixture
def comm():
    c = ht.WORLD
    if c.size < 2:
        pytest.skip("program-lint SPMD tests need a multi-device mesh")
    return c


@pytest.fixture(autouse=True)
def _clean_analyzer_state():
    prev = diagnostics.set_analysis_mode("0")
    analysis.clear_diagnostics()
    reset_dispatch_state()
    yield
    diagnostics.set_analysis_mode(prev)
    analysis.clear_diagnostics()
    reset_dispatch_state()
    dispatch.clear_cache()


class TestProgramLint:
    def _split2(self, comm):
        return NamedSharding(comm.mesh, P(comm.axis_name, None))

    def _repl(self, comm):
        return NamedSharding(comm.mesh, P())

    def test_implicit_unaccounted_collective_j101(self, comm):
        x = jax.device_put(jnp.ones((4 * comm.size, 4)), self._split2(comm))
        # a sum over the split axis: GSPMD inserts an all-reduce nothing
        # accounted -> the seeded "implicit unaccounted collective"
        diags = analyze(
            jax.jit(lambda a: a.sum(axis=0), out_shardings=self._repl(comm)), x
        )
        assert "J101" in rules(diags)
        d = next(d for d in diags if d.rule == "J101")
        assert d.details["collective"] == "all-reduce"

    def test_accounted_collective_clean(self, comm):
        x = jax.device_put(jnp.ones((4 * comm.size, 4)), self._split2(comm))

        def launch(a):
            with comm.account_implicit("psum", 16, site="kmeans.lloyd"):
                return a.sum(axis=0)

        assert analyze(jax.jit(launch, out_shardings=self._repl(comm)), x) == []

    def test_full_gather_j102(self, comm):
        x = jax.device_put(jnp.ones((4 * comm.size, 4)), self._split2(comm))
        # replicated output forces an all-gather of the whole split dim
        diags = analyze(
            jax.jit(lambda a: a * 2.0, out_shardings=self._repl(comm)), x
        )
        assert "J102" in rules(diags)
        d = next(d for d in diags if d.rule == "J102")
        assert d.details["result_shape"][0] == d.details["operand_shape"][0] * comm.size

    def test_weak_type_recompile_j103(self):
        # a Python scalar traced as an argument -> weak-type invar; the
        # seeded "weak-type recompile pair" (2.0 now, 2 later = 2 compiles)
        diags = analyze(lambda a, s: a * s, jnp.ones((8,)), 2.0)
        assert rules(diags) == ["J103"]
        assert diags[0].details["weak_invars"] == [1]

    def test_committed_scalar_clean(self):
        assert analyze(lambda a, s: a * s, jnp.ones((8,), jnp.float32),
                       jnp.float32(2.0)) == []

    def test_donation_miss_j104(self):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # jax's own donation warning
            diags = analyze(
                lambda a: a[:2].sum(), jnp.ones((16,)), donate_argnums=(0,)
            )
        assert "J104" in rules(diags)
        d = next(d for d in diags if d.rule == "J104")
        assert d.details["donate_argnums"] == [0] and d.details["aliased"] == []

    def test_successful_donation_clean(self):
        assert analyze(lambda a: a + 1.0, jnp.ones((16,)),
                       donate_argnums=(0,)) == []

    def test_silent_promotion_j105(self):
        diags = analyze(
            lambda a, b: a + b,
            jnp.ones((8,), jnp.float32), jnp.ones((8,), jnp.float64),
        )
        assert "J105" in rules(diags)
        d = next(d for d in diags if d.rule == "J105")
        assert d.details == {"from": "float32", "to": "float64", "invar": 0}

    def test_clean_kmeans_lloyd_step(self, comm):
        from heat_tpu.cluster.kmeans import _lloyd_body

        k, f = 4, 8
        x = ht.random.randn(8 * comm.size, f, split=0)
        xp = x.larray_padded
        centers = jnp.asarray(
            np.random.default_rng(0).standard_normal((k, f)), xp.dtype
        )

        def launch(xp_, centers_):
            nbytes = (k * f + k) * xp_.dtype.itemsize
            with comm.account_implicit("psum", nbytes, site="kmeans.lloyd"):
                return _lloyd_body(xp_, centers_, int(x.shape[0]), k)

        # PR 27: the fit rounds the points to bfloat16, once, for both of
        # its products, and says so in the program (kmeans._half_d2).  At
        # the jaxpr an explicit astype and a silent narrowing look alike,
        # so the caller declares it; undeclared, J201 names that one cast
        # of the input and nothing else
        assert analyze(launch, xp, centers, allowed_narrowing=("bfloat16",)) == []
        undeclared = [d for d in analyze(launch, xp, centers) if d.rule.startswith("J2")]
        assert [(d.rule, d.details) for d in undeclared] == [
            ("J201", {"from": xp.dtype.name, "to": "bfloat16", "is_input": True})
        ]

    def test_emit_flows_into_telemetry_and_ring(self):
        before = telemetry.snapshot().get("analysis.diags.J101", 0)
        diagnostics.emit(Diagnostic(rule="J101", message="m", location="l"),
                         mode="off")
        assert telemetry.snapshot()["analysis.diags.J101"] == before + 1
        recent = analysis.recent_diagnostics()
        assert recent[-1].rule == "J101" and recent[-1].location == "l"
        analysis.clear_diagnostics()
        assert analysis.recent_diagnostics() == []

    def test_warn_and_raise_modes(self):
        d = Diagnostic(rule="J104", message="boom")
        with pytest.warns(AnalysisWarning, match="J104"):
            diagnostics.emit(d, mode="warn")
        with pytest.raises(ProgramLintError) as ei:
            diagnostics.emit(d, mode="raise")
        assert ei.value.diagnostic is d

    def test_mode_parsing(self):
        prev = diagnostics.set_analysis_mode("raise")
        assert diagnostics.analysis_mode() == "raise"
        diagnostics.set_analysis_mode("1")
        assert diagnostics.analysis_mode() == "warn"
        diagnostics.set_analysis_mode(prev)
        with pytest.raises(ValueError):
            diagnostics.set_analysis_mode("loud")


class TestDispatchHook:
    BUF = jnp.ones((16,), jnp.float32)

    def _churn(self, op, dtypes=(np.float32, np.int32)):
        for dt in dtypes:
            dispatch.eager_apply(op, (self.BUF, dispatch.scalar_leaf(2, dt)))

    def test_scalar_dtype_churn_emits_j103(self):
        diagnostics.set_analysis_mode("warn")
        dispatch.clear_cache()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", AnalysisWarning)
            self._churn(jnp.add)
        recs = [d for d in analysis.recent_diagnostics()
                if d.rule == "J103" and d.source == "dispatch"]
        assert len(recs) == 1

    def test_raise_mode_propagates_through_fallback(self):
        # a raise-mode diagnostic is a verdict, not a transient compile
        # failure — it must NOT degrade into the eager compile-fallback
        diagnostics.set_analysis_mode("raise")
        dispatch.clear_cache()
        fallbacks = dispatch.cache_stats()["compile_fallbacks"]
        with pytest.raises(ProgramLintError):
            self._churn(jnp.subtract)
        assert dispatch.cache_stats()["compile_fallbacks"] == fallbacks

    def test_off_mode_records_nothing(self):
        assert diagnostics.analysis_mode() == "off"
        dispatch.clear_cache()
        self._churn(jnp.multiply)
        assert analysis.recent_diagnostics() == []

    def test_distinct_shapes_not_grouped(self):
        diagnostics.set_analysis_mode("warn")
        dispatch.clear_cache()
        dispatch.eager_apply(jnp.add, (self.BUF, jnp.ones((16,), jnp.float32)))
        dispatch.eager_apply(jnp.add, (self.BUF, jnp.ones((1,), jnp.float32)))
        assert analysis.recent_diagnostics() == []


# ----------------------------------------------------------------------
# satellite: os.urandom-backed default seeding (the H601 fix)
# ----------------------------------------------------------------------
class TestDefaultSeed:
    def test_entropy_backed_and_31_bit(self):
        draws = {ht.random.default_seed() for _ in range(8)}
        assert len(draws) > 1  # a clock in the same ms would collide
        assert all(0 <= s <= 0x7FFFFFFF for s in draws)

    def test_explicit_seed_stays_deterministic(self):
        ht.random.seed(42)
        a = np.asarray(ht.random.rand(5)._dense())
        ht.random.seed(42)
        b = np.asarray(ht.random.rand(5)._dense())
        np.testing.assert_array_equal(a, b)

    def test_unseeded_uses_default_seed(self, monkeypatch):
        from heat_tpu.core import random as hrandom

        monkeypatch.setattr(hrandom, "default_seed", lambda: 1234)
        hrandom.seed()
        assert hrandom.get_state()[1] == 1234
