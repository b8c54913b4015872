"""Precision & memory static analysis tests (ISSUE 12 tentpole).

The contract under test (docs/static_analysis.md, "Precision & memory
rules"):

* the jaxpr dtype-flow walker flags each J2xx hazard on a bad fixture
  and stays silent on the good twin — J201 unsanctioned float
  truncation, J202 long-axis low-precision accumulation (reductions AND
  scan carries), J203 unpinned low-precision contractions, J204
  precision-policy violations;
* the static peak-HBM estimator agrees with
  ``Compiled.memory_analysis()`` within 10% on real kernels, models
  donation aliasing and per-device sharding division, and emits J301
  against ``HEAT_TPU_HBM_BUDGET_BYTES``;
* the ``POLICIES`` registry is a pure literal covering every served
  estimator kind, the bf16 KMeans predict path passes its ``tolerance``
  contract while bitwise kinds ignore the knob bitwise-identically, and
  a mis-declared ``bitwise`` policy is REFUSED at registry load;
* the dispatch compile hook runs the new analyzers (scoped policy +
  peak estimates into /statusz), and ``python -m heat_tpu.analysis
  --rules J2,J3`` batch-checks the served predict programs;
* satellites: ``types.canonical_dtype`` property grid, the
  ``lint_gate.py --fix-stale`` pruning workflow over the now-empty
  baseline.
"""

import ast
import json
import os
import subprocess
import sys
import warnings

import jax
import jax.lax as lax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

import heat_tpu as ht
from heat_tpu import analysis
from heat_tpu.analysis import diagnostics, dtype_flow, memory_model
from heat_tpu.analysis import precision_policy as pp
from heat_tpu.analysis.precision_policy import POLICIES, PrecisionPolicyError
from heat_tpu.core import dispatch, types

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))

TOL_POLICY = {"mode": "tolerance", "rtol": 0.02,
              "compute_dtypes": ("float32", "bfloat16")}
BITWISE_POLICY = {"mode": "bitwise", "compute_dtypes": ("float32",)}


def rules(diags):
    return [d.rule for d in diags]


@pytest.fixture(autouse=True)
def _clean_state():
    prev = diagnostics.set_analysis_mode("0")
    prev_dt = pp.set_predict_dtype("")
    analysis.clear_diagnostics()
    memory_model.reset_estimates()
    yield
    diagnostics.set_analysis_mode(prev)
    pp.set_predict_dtype(prev_dt)
    analysis.clear_diagnostics()
    memory_model.reset_estimates()
    dispatch.clear_cache()


# ----------------------------------------------------------------------
# J201 — silent float truncation
# ----------------------------------------------------------------------
class TestJ201:
    X = jnp.ones((64, 8), jnp.float32)

    @staticmethod
    def _narrowing(a):
        return jnp.matmul(a.astype(jnp.bfloat16), a.astype(jnp.bfloat16).T,
                          preferred_element_type=jnp.float32)

    def test_unsanctioned_narrowing_flags(self):
        diags = dtype_flow.analyze_dtype_flow(self._narrowing, self.X)
        assert "J201" in rules(diags)
        d = next(d for d in diags if d.rule == "J201")
        assert d.details["from"] == "float32" and d.details["to"] == "bfloat16"

    def test_allowed_narrowing_clean(self):
        assert dtype_flow.analyze_dtype_flow(
            self._narrowing, self.X, allowed_narrowing=("bfloat16",)
        ) == []

    def test_tolerance_policy_sanctions(self):
        assert dtype_flow.analyze_dtype_flow(
            self._narrowing, self.X, policy=TOL_POLICY
        ) == []

    def test_bitwise_policy_does_not_sanction(self):
        got = rules(dtype_flow.analyze_dtype_flow(
            self._narrowing, self.X, policy=BITWISE_POLICY
        ))
        assert "J201" in got and "J204" in got

    def test_f64_to_f32_flags(self):
        x64 = jnp.ones((8,), jnp.float64)
        diags = dtype_flow.analyze_dtype_flow(
            lambda a: a.astype(jnp.float32) * 2.0, x64
        )
        assert rules(diags) == ["J201"]
        assert diags[0].details == {"from": "float64", "to": "float32",
                                    "is_input": True}

    def test_weak_scalar_and_widening_clean(self):
        # widening (J105's domain) and weak python scalars never J201
        assert dtype_flow.analyze_dtype_flow(
            lambda a, s: a.astype(jnp.float64) * s,
            jnp.ones((8,), jnp.float32), 2.0,
        ) == []


# ----------------------------------------------------------------------
# J202 — long-axis low-precision accumulation
# ----------------------------------------------------------------------
class TestJ202:
    XB = jnp.ones((4096, 8), jnp.bfloat16)

    @staticmethod
    def _bf16_reduce(a):
        return lax.reduce(a, np.asarray(0, jnp.bfloat16), lax.add, (0,))

    def test_long_axis_bf16_reduce_flags(self):
        diags = dtype_flow.analyze_dtype_flow(
            self._bf16_reduce, self.XB, allowed_narrowing=("bfloat16",)
        )
        assert rules(diags) == ["J202"]
        assert diags[0].details["extent"] == 4096
        assert diags[0].details["dtype"] == "bfloat16"

    def test_f32_accumulation_clean(self):
        def good(a):
            return lax.reduce(
                a.astype(jnp.float32), np.asarray(0, np.float32), lax.add, (0,)
            )
        assert dtype_flow.analyze_dtype_flow(good, self.XB) == []

    def test_short_axis_clean(self):
        short = jnp.ones((64, 8), jnp.bfloat16)
        assert dtype_flow.analyze_dtype_flow(self._bf16_reduce, short) == []

    def test_threshold_knob(self, monkeypatch):
        monkeypatch.setenv("HEAT_TPU_J202_THRESHOLD", "8192")
        assert dtype_flow.analyze_dtype_flow(self._bf16_reduce, self.XB) == []
        monkeypatch.setenv("HEAT_TPU_J202_THRESHOLD", "32")
        short = jnp.ones((64, 8), jnp.bfloat16)
        assert rules(dtype_flow.analyze_dtype_flow(self._bf16_reduce, short)) == ["J202"]

    def test_jnp_sum_upcasts_clean(self):
        # jnp.sum accumulates f32 internally — must NOT flag
        assert dtype_flow.analyze_dtype_flow(
            lambda a: jnp.sum(a, axis=0), self.XB
        ) == []

    def test_long_bf16_scan_carry_flags(self):
        def scanned(c, xs):
            def body(c, x):
                return c + x, ()
            out, _ = lax.scan(body, c, xs)
            return out

        diags = dtype_flow.analyze_dtype_flow(
            scanned, jnp.zeros((8,), jnp.bfloat16),
            jnp.ones((2048, 8), jnp.bfloat16),
        )
        assert "J202" in rules(diags)
        d = next(d for d in diags if d.rule == "J202")
        assert d.details["primitive"] == "scan" and d.details["extent"] == 2048

    def test_f32_scan_carry_clean(self):
        def scanned(c, xs):
            def body(c, x):
                return c + x, ()
            out, _ = lax.scan(body, c, xs)
            return out

        assert dtype_flow.analyze_dtype_flow(
            scanned, jnp.zeros((8,), jnp.float32),
            jnp.ones((2048, 8), jnp.float32),
        ) == []


# ----------------------------------------------------------------------
# J203 — unpinned low-precision contraction
# ----------------------------------------------------------------------
class TestJ203:
    XB = jnp.ones((64, 8), jnp.bfloat16)

    def test_unpinned_bf16_matmul_flags(self):
        diags = dtype_flow.analyze_dtype_flow(lambda a: jnp.matmul(a, a.T), self.XB)
        assert rules(diags) == ["J203"]
        assert diags[0].details["operand_dtypes"] == ["bfloat16", "bfloat16"]

    def test_preferred_element_type_clean(self):
        assert dtype_flow.analyze_dtype_flow(
            lambda a: jnp.matmul(a, a.T, preferred_element_type=jnp.float32),
            self.XB,
        ) == []

    def test_highest_precision_clean(self):
        assert dtype_flow.analyze_dtype_flow(
            lambda a: jnp.matmul(a, a.T, precision=jax.lax.Precision.HIGHEST),
            self.XB,
        ) == []

    def test_f32_matmul_clean(self):
        x = jnp.ones((64, 8), jnp.float32)
        assert dtype_flow.analyze_dtype_flow(lambda a: jnp.matmul(a, a.T), x) == []


# ----------------------------------------------------------------------
# J204 — policy violations (walker-level; the choke points below)
# ----------------------------------------------------------------------
class TestJ204:
    def test_bf16_under_bitwise_flags(self):
        diags = dtype_flow.analyze_dtype_flow(
            lambda a: jnp.matmul(a, a.T, preferred_element_type=jnp.float32),
            jnp.ones((8, 8), jnp.bfloat16), policy=BITWISE_POLICY,
        )
        assert rules(diags) == ["J204"]
        assert diags[0].details["outside"] == ["bfloat16"]

    def test_bf16_under_tolerance_clean(self):
        assert dtype_flow.analyze_dtype_flow(
            lambda a: jnp.matmul(a, a.T, preferred_element_type=jnp.float32),
            jnp.ones((8, 8), jnp.bfloat16), policy=TOL_POLICY,
        ) == []

    def test_wider_than_native_not_a_violation(self):
        # f64 data through an f32-declared estimator IS the native path
        assert dtype_flow.analyze_dtype_flow(
            lambda a: a * 2.0, jnp.ones((8,), jnp.float64),
            policy=BITWISE_POLICY,
        ) == []

    def test_disallowed_predict_dtype_emits_once(self):
        pp.set_predict_dtype("bfloat16")
        before = len([d for d in analysis.recent_diagnostics()
                      if d.rule == "J204"])
        assert pp.compute_dtype("Lasso") == "float32"  # bitwise: knob ignored
        assert pp.compute_dtype("Lasso") == "float32"
        after = [d for d in analysis.recent_diagnostics() if d.rule == "J204"]
        assert len(after) == before + 1  # warned once, not per call
        assert pp.compute_dtype("KMeans") == "bfloat16"  # tolerance: honored


# ----------------------------------------------------------------------
# static peak-HBM estimator (J301)
# ----------------------------------------------------------------------
def _xla_peak(fn, args, donate=()):
    jf = jax.jit(fn, donate_argnums=donate)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        ma = jf.lower(*args).compile().memory_analysis()
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes"):
        if not hasattr(ma, attr):
            pytest.skip("Compiled.memory_analysis lacks size attributes here")
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


class TestMemoryModel:
    N = 256

    def _check(self, fn, args, donate=()):
        est = memory_model.estimate_peak(fn, *args, donate_argnums=donate)
        xla = _xla_peak(fn, args, donate)
        assert xla > 0
        # acceptance bound: the static prediction within 10% of XLA's
        # own memory analysis
        assert abs(est.per_device_bytes - xla) / xla < 0.10, (est, xla)
        return est

    def test_matmul_within_10pct(self):
        a = jnp.ones((self.N, self.N))
        self._check(lambda x, y: x @ y, (a, a))

    def test_elementwise_chain_within_10pct(self):
        a = jnp.ones((self.N, self.N))
        self._check(lambda x, y, z: x * y + z, (a, a, a))

    def test_reduction_within_10pct(self):
        a = jnp.ones((self.N, self.N))
        self._check(lambda x: x.sum(), (a,))

    def test_donated_update_within_10pct(self):
        a = jnp.ones((self.N, self.N))
        est = self._check(lambda x: x + 1.0, (a,), donate=(0,))
        assert est.aliased_bytes == a.nbytes

    def test_donation_halves_liveness(self):
        a = jnp.ones((1024, 1024))
        plain = memory_model.estimate_peak(lambda x: x + 1.0, a)
        donated = memory_model.estimate_peak(
            lambda x: x + 1.0, a, donate_argnums=(0,)
        )
        assert donated.per_device_bytes == plain.per_device_bytes - a.nbytes

    def test_sharded_division(self):
        comm = ht.WORLD
        if comm.size < 2:
            pytest.skip("needs a multi-device mesh")
        x = jax.device_put(
            jnp.ones((64 * comm.size, 16)),
            NamedSharding(comm.mesh, P(comm.axis_name, None)),
        )
        est = memory_model.estimate_peak(lambda v: v * 2.0, x)
        assert est.peak_bytes == 2 * x.nbytes
        assert est.per_device_bytes == est.peak_bytes // comm.size

    def test_budget_bad_good_fixture(self, monkeypatch):
        a = jnp.ones((512, 512))
        est = memory_model.estimate_peak(lambda x: x @ x, a)
        monkeypatch.setenv("HEAT_TPU_HBM_BUDGET_BYTES", str(est.per_device_bytes - 1))
        d = memory_model.check_budget(est, "fixture")
        assert d is not None and d.rule == "J301"
        assert d.details["budget_bytes"] == est.per_device_bytes - 1
        # good twin: a budget the program fits under
        monkeypatch.setenv("HEAT_TPU_HBM_BUDGET_BYTES", str(est.per_device_bytes))
        assert memory_model.check_budget(est, "fixture") is None
        monkeypatch.delenv("HEAT_TPU_HBM_BUDGET_BYTES")
        assert memory_model.check_budget(est, "fixture") is None  # unarmed

    def test_analyze_surfaces_j301(self, monkeypatch):
        monkeypatch.setenv("HEAT_TPU_HBM_BUDGET_BYTES", "64")
        diags = analysis.analyze(lambda x: x * 2.0, jnp.ones((1024,)))
        assert "J301" in rules(diags)


# ----------------------------------------------------------------------
# the POLICIES registry
# ----------------------------------------------------------------------
class TestPoliciesRegistry:
    def test_pure_literal(self):
        src = open(os.path.join(
            REPO_ROOT, "heat_tpu", "analysis", "precision_policy.py"
        )).read()
        tree = ast.parse(src)
        table = None
        for node in tree.body:
            if isinstance(node, ast.Assign) and any(
                getattr(t, "id", None) == "POLICIES" for t in node.targets
            ):
                table = ast.literal_eval(node.value)
        assert table == POLICIES  # statically parseable, value-identical

    def test_covers_every_served_kind(self):
        from heat_tpu.serving.model_io import SUPPORTED_KINDS

        assert set(POLICIES) == set(SUPPORTED_KINDS)
        for kind, pol in POLICIES.items():
            assert pol["mode"] in ("bitwise", "tolerance")
            assert pol["compute_dtypes"][0] == "float32"
            if pol["mode"] == "tolerance":
                assert pol["rtol"] > 0
            else:
                assert len(pol["compute_dtypes"]) == 1

    def test_validate_policy_rejects_malformed(self):
        with pytest.raises(ValueError):
            pp.validate_policy({"mode": "loose", "compute_dtypes": ("float32",)})
        with pytest.raises(ValueError):
            pp.validate_policy({"mode": "tolerance", "compute_dtypes": ("float32",)})
        with pytest.raises(ValueError):
            pp.validate_policy({"mode": "bitwise", "compute_dtypes": ("int7",)})
        ok = pp.validate_policy(
            {"mode": "tolerance", "rtol": 0.1, "compute_dtypes": ["float32"]}
        )
        assert ok["compute_dtypes"] == ("float32",)

    def test_scope_nesting_and_reset(self):
        assert pp.active_policy() is None
        with pp.scope("KMeans"):
            assert pp.active_policy()["mode"] == "tolerance"
            with pp.scope("Lasso"):
                assert pp.active_policy()["mode"] == "bitwise"
            assert pp.active_policy()["mode"] == "tolerance"
        assert pp.active_policy() is None


# ----------------------------------------------------------------------
# the bf16 KMeans predict path (tolerance) vs bitwise kinds
# ----------------------------------------------------------------------
def _blobs(n=192, f=8, k=4, spread=8.0):
    rng = np.random.default_rng(7)
    centers = rng.standard_normal((k, f)) * spread
    x = centers[rng.integers(0, k, n)] + rng.standard_normal((n, f))
    return ht.array(x.astype(np.float32), split=None), centers.astype(np.float32)


class TestBf16Predict:
    def test_tolerance_gate_kmeans(self):
        # seeded at the true blob centers: every sample's margin to the
        # runner-up center is >> the bf16 distance error, so the
        # tolerance path must reproduce the labels exactly
        x, centers = _blobs(spread=16.0)
        km = ht.cluster.KMeans(n_clusters=4, init=ht.array(centers),
                               max_iter=4, random_state=0)
        km.fit(x)
        ref = np.asarray(km.predict(x)._dense())
        pp.set_predict_dtype("bfloat16")
        low = np.asarray(km.predict(x)._dense())
        # well-separated blobs: the tolerance-path labels must agree
        np.testing.assert_array_equal(ref, low)

        # and the compute core (the squared distances argmin compares)
        # stays inside the declared rtol of its scale
        from heat_tpu.spatial import distance

        xd, cd = x._dense(), km.cluster_centers_._dense()
        d_ref = np.asarray(distance._pairwise_sqeuclidean(xd, cd))
        d_low = np.asarray(distance._pairwise_sqeuclidean_bf16(xd, cd))
        scale = np.abs(d_ref).max()
        assert np.abs(d_ref - d_low).max() / scale < POLICIES["KMeans"]["rtol"]

    def test_bf16_program_is_j2_clean_under_scope(self):
        # the shipped low-precision op must pass its own lint: narrowing
        # sanctioned by the tolerance policy, accumulation pinned f32
        from heat_tpu.spatial import distance

        x = jnp.ones((32, 8), jnp.float32)
        diags = dtype_flow.analyze_dtype_flow(
            distance._pairwise_euclidean_bf16, x, x,
            policy=POLICIES["KMeans"],
        )
        assert diags == []
        # and unsanctioned it is exactly the J201 hazard (non-vacuous)
        assert "J201" in rules(dtype_flow.analyze_dtype_flow(
            distance._pairwise_euclidean_bf16, x, x
        ))

    def test_bitwise_kind_ignores_knob(self):
        x, _ = _blobs()
        kmed = ht.cluster.KMedians(n_clusters=4, init="random", max_iter=5,
                                   random_state=0)
        kmed.fit(x)
        ref = np.asarray(kmed.predict(x)._dense())
        pp.set_predict_dtype("bfloat16")
        again = np.asarray(kmed.predict(x)._dense())
        np.testing.assert_array_equal(ref, again)  # bitwise: knob is inert

    @staticmethod
    def _labeled_blobs(n, k=4, f=8, spread=16.0, seed=7):
        # labels = blob membership: every k-neighborhood is label-pure,
        # so a bf16 near-tie that permutes WHICH same-blob neighbors are
        # kept cannot change the vote — the label-bitwise contract is a
        # statement about margins, not about exact neighbor identity
        rng = np.random.default_rng(seed)
        centers = rng.standard_normal((k, f)) * spread
        assign = rng.integers(0, k, n)
        x = centers[assign] + rng.standard_normal((n, f))
        return x.astype(np.float32), assign.astype(np.int32)

    def test_knn_bf16_labels_bitwise(self):
        # the KNN tolerance contract covers the distance stage only: on
        # margin-separated blobs the bf16 neighbor search must
        # reproduce the predicted labels EXACTLY (ISSUE 16 satellite)
        xd, lab_d = self._labeled_blobs(160)
        x = ht.array(xd, split=None)
        lab = ht.array(lab_d, split=None)
        knn = ht.classification.KNeighborsClassifier(n_neighbors=5)
        knn.fit(x, lab)
        ref = np.asarray(knn.predict(x)._dense())
        np.testing.assert_array_equal(ref, lab_d)  # sane reference
        pp.set_predict_dtype("bfloat16")
        low = np.asarray(knn.predict(x)._dense())
        np.testing.assert_array_equal(ref, low)

    def test_knn_bf16_distributed_ring_labels_bitwise(self):
        # split inputs take the ring-fused top-k; the lowp tile swap is
        # part of its cache key, so both variants coexist compiled
        if ht.WORLD.size < 2:
            pytest.skip("needs a multi-device mesh")
        xd, lab_d = self._labeled_blobs(192, seed=13)
        xs = ht.array(xd, split=0)
        lab = ht.array(lab_d, split=0)
        knn = ht.classification.KNeighborsClassifier(n_neighbors=5)
        knn.fit(xs, lab)
        ref = np.asarray(knn.predict(xs)._dense())
        pp.set_predict_dtype("bfloat16")
        low = np.asarray(knn.predict(xs)._dense())
        np.testing.assert_array_equal(ref, low)

    def test_pca_transform_bf16_within_rtol(self):
        x, _ = _blobs(n=256, f=8)
        pca = ht.decomposition.PCA(n_components=4, svd_solver="full")
        pca.fit(x)
        ref = np.asarray(pca.transform(x)._dense())
        pp.set_predict_dtype("bfloat16")
        low = np.asarray(pca.transform(x)._dense())
        assert low.dtype == np.float32  # accumulation stayed pinned f32
        scale = np.abs(ref).max()
        assert np.abs(ref - low).max() / scale < POLICIES["PCA"]["rtol"]


# ----------------------------------------------------------------------
# registry enforcement (save_model -> ModelRegistry.load)
# ----------------------------------------------------------------------
class TestRegistryEnforcement:
    def _fitted_km(self):
        x, _ = _blobs()
        km = ht.cluster.KMeans(n_clusters=3, init="random", max_iter=4,
                               random_state=0)
        km.fit(x)
        return km

    def test_policy_recorded_and_roundtrips(self, tmp_path):
        from heat_tpu import serving

        km = self._fitted_km()
        serving.save_model(km, str(tmp_path), version=1, name="km")
        reg = serving.ModelRegistry()
        assert reg.load("km", str(tmp_path)) == 1
        rec = reg.record("km")
        assert rec["policy"]["mode"] == "tolerance"
        assert rec["meta"]["compute_dtype"] == "float32"

    def test_misdeclared_bitwise_rejected_at_load(self, tmp_path):
        from heat_tpu import serving

        km = self._fitted_km()
        pp.set_predict_dtype("bfloat16")  # export computes bf16...
        serving.save_model(
            km, str(tmp_path), version=1, name="km",
            policy={"mode": "bitwise", "compute_dtypes": ("float32",)},
        )  # ...while declaring bitwise f32
        reg = serving.ModelRegistry()
        with pytest.raises(PrecisionPolicyError) as ei:
            reg.load("km", str(tmp_path))
        assert ei.value.diagnostic.rule == "J204"
        # the refusal left the registry empty — nothing half-activated
        assert reg.model_names() == []

    def test_refusal_keeps_active_version_serving(self, tmp_path):
        from heat_tpu import serving

        km = self._fitted_km()
        good_dir, bad_dir = tmp_path / "good", tmp_path / "bad"
        serving.save_model(km, str(good_dir), version=1, name="km")
        pp.set_predict_dtype("bfloat16")
        serving.save_model(
            km, str(bad_dir), version=2, name="km",
            policy={"mode": "bitwise", "compute_dtypes": ("float32",)},
        )
        pp.set_predict_dtype("")
        reg = serving.ModelRegistry()
        reg.load("km", str(good_dir))
        with pytest.raises(PrecisionPolicyError):
            reg.load("km", str(bad_dir), version=2)
        assert reg.active_version("km") == 1  # canary refused, v1 serving

    def test_bitwise_process_rejects_tolerance_export(self, tmp_path):
        # exported under bf16, loaded into a process ALSO serving bf16:
        # fine for the tolerance policy; the same version re-declared
        # is covered above — here the recorded dtype check alone
        from heat_tpu import serving

        km = self._fitted_km()
        pp.set_predict_dtype("bfloat16")
        serving.save_model(km, str(tmp_path), version=1, name="km")
        reg = serving.ModelRegistry()
        assert reg.load("km", str(tmp_path)) == 1  # tolerance allows bf16
        assert reg.record("km")["meta"]["compute_dtype"] == "bfloat16"

    def test_legacy_meta_loads_unchecked(self, tmp_path):
        from heat_tpu import serving

        km = self._fitted_km()
        serving.save_model(km, str(tmp_path), version=1, name="km")
        # strip the policy fields the way a pre-ISSUE-12 writer would
        meta_path = os.path.join(str(tmp_path), "meta_1.json")
        meta = json.load(open(meta_path))
        meta.pop("policy", None)
        meta.pop("compute_dtype", None)
        from heat_tpu.resilience.atomic import atomic_write

        with atomic_write(meta_path) as tmp:
            with open(tmp, "w") as fh:
                json.dump(meta, fh)
        reg = serving.ModelRegistry()
        assert reg.load("km", str(tmp_path)) == 1


# ----------------------------------------------------------------------
# the dispatch compile hook + introspection surfaces
# ----------------------------------------------------------------------
class TestDispatchHookPrecision:
    def test_scoped_policy_checks_dispatch_compiles(self):
        diagnostics.set_analysis_mode("warn")
        dispatch.clear_cache()
        xb = jnp.ones((16, 8), jnp.bfloat16)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            with pp.scope("Lasso"):  # bitwise f32
                dispatch.eager_apply(jnp.matmul, (xb, xb.T))
        got = rules(analysis.recent_diagnostics())
        assert "J203" in got and "J204" in got

    def test_unscoped_bf16_dispatch_flags_j203_only(self):
        diagnostics.set_analysis_mode("warn")
        dispatch.clear_cache()
        xb = jnp.ones((16, 8), jnp.bfloat16)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            dispatch.eager_apply(jnp.matmul, (xb, xb.T))
        got = rules(analysis.recent_diagnostics())
        assert "J203" in got and "J204" not in got

    def test_estimates_recorded_and_budget_fires(self, monkeypatch):
        monkeypatch.setenv("HEAT_TPU_HBM_BUDGET_BYTES", "128")
        diagnostics.set_analysis_mode("warn")
        dispatch.clear_cache()
        memory_model.reset_estimates()
        x = jnp.ones((1024, 8), jnp.float32)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            dispatch.eager_apply(jnp.add, (x, x))
        assert "J301" in rules(analysis.recent_diagnostics())
        summary = memory_model.peak_summary()
        assert summary["budget_bytes"] == 128
        assert any(
            rec["per_device_bytes"] > 128 for rec in summary["estimates"].values()
        )

    def test_off_mode_records_nothing(self):
        assert diagnostics.analysis_mode() == "off"
        dispatch.clear_cache()
        memory_model.reset_estimates()
        xb = jnp.ones((16, 8), jnp.bfloat16)
        dispatch.eager_apply(jnp.matmul, (xb, xb.T))
        assert analysis.recent_diagnostics() == []
        assert memory_model.peak_summary()["estimates"] == {}

    def test_statusz_carries_analysis_section(self):
        diagnostics.set_analysis_mode("warn")
        dispatch.clear_cache()
        x = jnp.ones((64,), jnp.float32)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            dispatch.eager_apply(jnp.multiply, (x, x))
        from heat_tpu.telemetry.server import statusz_report

        doc = statusz_report()
        assert doc["analysis"]["mode"] == "warn"
        assert doc["analysis"]["hbm"]["estimates"]  # the estimate landed

    def test_crash_bundle_carries_analysis_section(self):
        from heat_tpu.telemetry.flight_recorder import build_bundle

        diagnostics.emit(
            analysis.Diagnostic(rule="J301", message="m", location="l"),
            mode="off",
        )
        doc = build_bundle(reason="test")
        recent = doc["analysis"]["recent_diagnostics"]
        assert any(d["rule"] == "J301" for d in recent)


# ----------------------------------------------------------------------
# the --rules J2,J3 batch CLI
# ----------------------------------------------------------------------
class TestProgramBatchCLI:
    def test_served_predict_programs_are_clean(self, capsys):
        from heat_tpu.analysis.__main__ import main

        assert main(["--rules", "J2,J3", "--format", "json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert set(doc["programs"]) == set(POLICIES)
        for kind, rec in doc["programs"].items():
            assert rec["diagnostics"] == []
        # the batch measured real programs, not nothing
        assert doc["programs"]["KMeans"]["predicted_peak_bytes"] > 0


# ----------------------------------------------------------------------
# satellite: types.canonical_dtype property grid (PR 1/8 invariants)
# ----------------------------------------------------------------------
GRID_DTYPES = [
    types.int8, types.int16, types.int32, types.int64,
    types.uint8, types.uint16, types.uint32, types.uint64,
    types.float16, types.bfloat16, types.float32, types.float64,
    types.complex64, types.complex128,
]


class TestCanonicalDtype:
    @pytest.mark.parametrize("t", GRID_DTYPES, ids=lambda t: t.__name__)
    def test_idempotent(self, t):
        once = types.canonical_dtype(t)
        assert types.canonical_dtype(once) == once

    @pytest.mark.parametrize("t", GRID_DTYPES, ids=lambda t: t.__name__)
    def test_never_widens_same_kind(self, t):
        # the J105 invariant: the canonical dtype is the same kind at
        # equal-or-smaller width — routing an astype through it can
        # never introduce silent same-kind widening
        req = np.dtype(t.jax_type())
        got = np.dtype(types.canonical_dtype(t))
        assert got.kind == req.kind or {got.kind, req.kind} <= {"V", "f"}
        assert got.itemsize <= req.itemsize

    @pytest.mark.parametrize("t", GRID_DTYPES, ids=lambda t: t.__name__)
    def test_spelling_agreement(self, t):
        # every spelling the migrated call sites use resolves identically
        jt = t.jax_type()
        expect = types.canonical_dtype(t)
        assert types.canonical_dtype(jt) == expect
        assert types.canonical_dtype(np.dtype(jt).name) == expect

    def test_x64_identity(self):
        # the suite runs with jax_enable_x64 — canonical is the identity
        assert jax.config.jax_enable_x64
        for t in GRID_DTYPES:
            assert np.dtype(types.canonical_dtype(t)) == np.dtype(t.jax_type())

    def test_x64_off_demotions(self):
        # the other half of the contract needs an x64-less process
        code = (
            "import jax, jax.numpy as jnp\n"
            "from heat_tpu.core import types\n"
            "assert not jax.config.jax_enable_x64\n"
            "import numpy as np\n"
            "pairs = {types.int64: jnp.int32, types.uint64: jnp.uint32,\n"
            "         types.float64: jnp.float32, types.complex128: jnp.complex64,\n"
            "         types.int32: jnp.int32, types.float32: jnp.float32,\n"
            "         types.bfloat16: jnp.bfloat16}\n"
            "for t, want in pairs.items():\n"
            "    got = types.canonical_dtype(t)\n"
            "    assert np.dtype(got) == np.dtype(want), (t, got)\n"
        )
        env = {k: v for k, v in os.environ.items() if k != "JAX_ENABLE_X64"}
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           cwd=REPO_ROOT, capture_output=True, text=True)
        assert r.returncode == 0, r.stderr

    def test_call_site_agreement(self):
        # the PR 1/8 migrated sites produce exactly the canonical index
        # dtype (no UserWarning-spam astype requests)
        want = np.dtype(types.canonical_dtype(jnp.int64))
        from heat_tpu.core import statistics

        am = statistics.argmin(ht.array(np.ones((4, 2), np.float32)), axis=1)
        assert np.asarray(am._dense()).dtype == want


# ----------------------------------------------------------------------
# satellite: lint baseline at zero + --fix-stale pruning
# ----------------------------------------------------------------------
class TestLintGateFixStale:
    def test_repo_baseline_is_empty(self):
        doc = json.load(open(os.path.join(REPO_ROOT, "scripts",
                                          "lint_baseline.json")))
        assert doc["violations"] == []

    def test_repo_lints_clean_with_empty_baseline(self):
        from lint_gate import run_gate

        res = run_gate(quiet=True)
        assert res["new_count"] == 0 and res["baseline"] == 0

    def test_fix_stale_prunes_without_accepting(self, tmp_path):
        from lint_gate import run_gate

        d = tmp_path / "src"
        d.mkdir()
        (d / "mod.py").write_text("try:\n    go()\nexcept Exception:\n    pass\n")
        baseline = tmp_path / "b.json"
        run_gate(paths=[str(d)], baseline_path=str(baseline), update=True,
                 quiet=True)
        # fix the accepted violation, introduce a NEW one elsewhere
        (d / "mod.py").write_text("try:\n    go()\nexcept ValueError:\n    pass\n")
        (d / "new.py").write_text('f = open(p, "w")\n')
        res = run_gate(paths=[str(d)], baseline_path=str(baseline),
                       fix_stale=True, quiet=True)
        assert res["fixed_count"] == 1
        assert res["new_count"] == 1  # the gate still fails on the new one
        doc = json.load(open(baseline))
        assert doc["violations"] == []  # pruned, NOT regenerated-with-new
        res2 = run_gate(paths=[str(d)], baseline_path=str(baseline), quiet=True)
        assert res2["fixed_count"] == 0 and res2["new_count"] == 1
