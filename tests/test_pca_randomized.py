"""``PCA(svd_solver="randomized")`` against the exact PCA, and its spans.

The reference is plain float64 numpy: the column means and the ``eigh`` of
the covariance (``n - 1`` in the denominator).  The tables have a planted
spectrum: ``n_components`` directions whose scales fall geometrically from 1
to 0.1, the rest at 1e-3, rotated off the coordinate axes, and column means
1,000 to 4,500 times that trailing spread, so a fit that did not centre each
product where it reads the table, or that lost the means' digits, shows.  The
hundredfold gap after the components asked for makes the randomized answer
the exact one to float32 rounding once one power iteration runs.  Row counts
are odd: over the suite's eight devices the table comes padded, and the
padding must count for nothing.

The compiled program at the benchmark cell's size (no table-sized temporary,
the reads of the table) is held in ``tests/test_chip_compile.py``, the one
file that compiles for a described chip.
"""

import os
import sys
import threading

import jax
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.core import dispatch
from heat_tpu.decomposition import pca
from heat_tpu.parallel.comm import Communication

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.run import load_py  # noqa: E402

ROOT = "ht.decomposition.PCA.fit"
FEATURES = 64

# float32 rounding, read on the CPU over the cases below: singular values
# within 9e-7 of the exact ones (relative), the subspaces' largest angle 9e-7,
# a component 3.3e-6 from the reference's, a mean 3e-6 of its column's spread
# off (the mean stored in float32 is half an ulp of 4.5 off at best, 1.2e-7,
# and the smallest spread here is 0.04) and the variance ratios 1.8e-6; each
# limit is some ten times its largest reading.  Without power iterations
# (`iterated_power` 0) a singular value reads 2.5e-4 off: refused.
SV_REL, SINE, COMP, MEAN, EVR = 1e-5, 1e-5, 4e-5, 3e-5, 2e-5


def _planted(rows, k, seed):
    rng = np.random.default_rng(seed)
    scales = np.full(FEATURES, 1e-3)
    scales[:k] = np.geomspace(1.0, 0.1, k)
    rot, _ = np.linalg.qr(rng.standard_normal((FEATURES, FEATURES)))
    means = rng.uniform(1.0, 4.5, FEATURES)
    return ((rng.standard_normal((rows, FEATURES)) * scales) @ rot.T + means).astype(np.float32)


def _exact(x, k):
    x = x.astype(np.float64)
    mean = x.mean(axis=0)
    cov = (x - mean).T @ (x - mean) / (len(x) - 1)
    lam, v = np.linalg.eigh(cov)
    lam, v = lam[::-1], v[:, ::-1]
    return {"mean": mean, "spread": np.sqrt(np.diag(cov)), "sv": np.sqrt(lam[:k] * (len(x) - 1)),
            "components": v[:, :k].T, "ratio": lam[:k] / lam.sum()}


def _fit(x, k, power, seed=3, **kw):
    return ht.decomposition.PCA(n_components=k, svd_solver="randomized", iterated_power=power,
                                random_state=seed, **kw).fit(x)


@pytest.mark.parametrize("rows", [2999, 4001])
@pytest.mark.parametrize("k", [6, 16])
@pytest.mark.parametrize("power", [1, 4])
def test_randomized_pca_is_the_exact_pca(rows, k, power):
    data = _planted(rows, k, seed=rows + k)
    model = _fit(ht.array(data, split=0), k, power)
    ref = _exact(data, k)
    v = model.components_.numpy().astype(np.float64)
    sign = np.sign(np.sum(v * ref["components"], axis=1))
    assert v.shape == (k, FEATURES) and model.n_components_ == k
    assert np.max(np.abs(model.singular_values_.numpy() - ref["sv"]) / ref["sv"]) < SV_REL
    assert np.linalg.norm(v - (v @ ref["components"].T) @ ref["components"], 2) < SINE
    assert np.max(np.linalg.norm(v * sign[:, None] - ref["components"], axis=1)) < COMP
    assert np.max(np.abs(model.mean_.numpy() - ref["mean"]) / ref["spread"]) < MEAN
    assert np.max(np.abs(model.explained_variance_ratio_.numpy() - ref["ratio"]) / ref["ratio"]) < EVR
    ev = model.explained_variance_.numpy()
    assert np.allclose(ev, model.singular_values_.numpy() ** 2 / (rows - 1), rtol=1e-6)
    assert model.total_explained_variance_ratio_ == pytest.approx(float(np.sum(ref["ratio"])), rel=EVR)


@pytest.mark.parametrize("split", [None, 1])
def test_other_splits_fit_alike(split):
    """A table that is not split by rows takes the program as it lies: the
    same answer, to the same limits."""
    data = _planted(1999, 6, seed=5)
    model = _fit(ht.array(data, split=split), 6, 2)
    ref = _exact(data, 6)
    assert np.max(np.abs(model.singular_values_.numpy() - ref["sv"]) / ref["sv"]) < SV_REL
    assert np.max(np.abs(model.mean_.numpy() - ref["mean"]) / ref["spread"]) < MEAN


def test_auto_is_no_power_iteration():
    """``iterated_power="auto"`` is 0 here (scikit-learn's rule would take 4
    or 7), and on a planted gap that is far from the exact answer."""
    data = _planted(2999, 6, seed=9)
    x = ht.array(data, split=0)
    auto, zero = _fit(x, 6, "auto"), _fit(x, 6, 0)
    assert np.array_equal(auto.components_.numpy(), zero.components_.numpy())
    ref = _exact(data, 6)
    assert np.max(np.abs(auto.singular_values_.numpy() - ref["sv"]) / ref["sv"]) > 5 * SV_REL


def test_the_fit_is_seeded_by_random_state():
    data = _planted(2999, 6, seed=2)
    x = ht.array(data, split=0)
    a, b, c = _fit(x, 6, 0, seed=11), _fit(x, 6, 0, seed=11), _fit(x, 6, 0, seed=12)
    assert np.array_equal(a.components_.numpy(), b.components_.numpy())
    assert not np.array_equal(a.components_.numpy(), c.components_.numpy())


@pytest.fixture()
def one_device():
    ht.use_comm(Communication(jax.devices()[:1]))
    prev = telemetry.set_tracing(True)
    try:
        yield
    finally:
        telemetry.set_tracing(prev)
        telemetry.clear_spans()
        ht.use_comm(ht.WORLD)


@pytest.mark.parametrize("power", [0, 4])
def test_fit_leaves_its_spans(one_device, power):
    """The root carries the plan and the reads of the table a fit makes: the
    moments' one and two a turn of the solver's ``power + 1``; inside it the
    two stages, moments and solver."""
    x = ht.array(_planted(999, 6, seed=1), split=0)
    _fit(x, 6, power)
    telemetry.clear_spans()
    _fit(x, 6, power)
    spans = sorted((r for r in telemetry.get_spans() if r.name in (ROOT, "pca.stage")), key=lambda r: r.start_ns)
    assert [r.name for r in spans] == [ROOT, "pca.stage", "pca.stage"]
    root, moments, solver = spans
    assert root.depth == 0 and root.attrs == {"rows": 999, "features": FEATURES, "split": 0, "solver": "randomized",
                                              "components": 6, "passes": 2 * power + 3}
    assert moments.attrs == {"stage": "moments"} and solver.attrs == {"stage": "solver", "solver": "randomized"}
    end = root.start_ns + root.duration_ns
    assert all(root.start_ns <= r.start_ns and r.start_ns + r.duration_ns <= end and r.depth == 1 for r in (moments, solver))
    assert {r.thread_id for r in spans} == {threading.get_ident()}


def test_two_programs_a_fit_and_nothing_read_back(one_device):
    """The moments are one program of the dispatch layer, the solver one
    launch of its own; nothing comes back to the host inside ``fit``."""
    x = ht.array(_planted(999, 6, seed=1), split=0)
    _fit(x, 6, 2)
    before = dispatch.cache_stats()
    with jax.transfer_guard_device_to_host("disallow"):
        model = _fit(x, 6, 2)
    after = dispatch.cache_stats()
    assert after["external_dispatches"] - before["external_dispatches"] == 1
    assert after["dispatches"] - before["dispatches"] == 1
    assert isinstance(model._tevr, jax.Array)


def test_a_resumed_fit_counts_only_the_solvers_reads(one_device, tmp_path):
    from heat_tpu import resilience as rz

    x = ht.array(_planted(999, 6, seed=1), split=0)
    with rz.fault_plan({"pca.stage": [{"at": 1, "kind": "permanent"}]}):
        with pytest.raises(rz.PermanentFault):
            _fit(x, 6, 2, checkpoint_every=1, checkpoint_dir=str(tmp_path))
    telemetry.clear_spans()
    resumed = _fit(x, 6, 2, checkpoint_every=1, resume_from=str(tmp_path))
    (root,) = [r for r in telemetry.get_spans() if r.name == ROOT]
    assert root.attrs["passes"] == 2 * 2 + 2
    assert np.array_equal(resumed.components_.numpy(), _fit(x, 6, 2).components_.numpy())


def test_a_checkpoint_of_the_mean_alone_resumes(one_device, tmp_path):
    """A fit stopped between the stages by an older version saved the mean
    and no variances: the resumed fit takes the saved mean, reads the table
    once more for the variances, and comes to the exact PCA."""
    from heat_tpu.core import statistics
    from heat_tpu.utils.checkpoint import Checkpointer

    data = _planted(999, 6, seed=1)
    x = ht.array(data, split=0)
    Checkpointer(str(tmp_path)).save(0, {"stage": "mean", "mean": np.asarray(statistics.mean(x, axis=0)._dense())})
    telemetry.clear_spans()
    resumed = _fit(x, 6, 2, checkpoint_every=1, resume_from=str(tmp_path))
    (root,) = [r for r in telemetry.get_spans() if r.name == ROOT]
    assert root.attrs["passes"] == 2 * 2 + 3
    ref = _exact(data, 6)
    assert np.max(np.abs(resumed.singular_values_.numpy() - ref["sv"]) / ref["sv"]) < SV_REL
    ratio = resumed.explained_variance_ratio_.numpy()
    assert np.max(np.abs(ratio - ref["ratio"]) / ref["ratio"]) < EVR


@pytest.mark.parametrize("metric,want", [("pca_table_reads", 7), ("pca_fit_host_ms", None)])
def test_the_benchmarks_span_readers(one_device, metric, want):
    x = ht.array(_planted(999, 6, seed=1), split=0)
    _fit(x, 6, 2)
    telemetry.clear_spans()
    for _ in range(3):
        _fit(x, 6, 2)
    reader = load_py("layer_metrics", metric)
    run = {"solves": 3, "notes": {}}
    value = reader.read(run)
    assert run["notes"] == {} and (value == want if want is not None else value > 0)
    if metric == "pca_fit_host_ms":
        roots = [r for r in telemetry.get_spans() if r.name == ROOT]
        assert value == pytest.approx(sum(r.duration_ns for r in roots) / 3e6)
    # a program without the root span (an older one): nothing, and the reason
    telemetry.clear_spans()
    run = {"solves": 3, "notes": {}}
    assert reader.read(run) is None and metric in run["notes"]


def _trace_run(work, products_s, sketch_s, rest_s, moments_s=0.0005, solves=2, ops=None):
    n, f, ell = work["rows"], work["features"], work["ell"]
    top = ops if ops is not None else [
        [f"%fusion.5561 fusion f32[{ell},{n}]", solves * products_s / 2],
        [f"%fusion.5579 fusion f32[{ell},{f}]", solves * products_s / 2],
        [f"%fusion.5570 fusion f32[{n},{ell}]", solves * sketch_s / 2],
        [f"%fusion.5578 fusion f32[{n},{ell}]", solves * sketch_s / 4],
        [f"%fusion.5562 fusion f32[{ell},{ell}]", solves * sketch_s / 4],
        [f"%subtract_reduce_fusion.1 fusion (f32[{f}], f32[{f}])", solves * moments_s],
        ["%custom-call.236 custom-call:EighTpu (f32[128], f32[128])", solves * rest_s]]
    return {"solves": solves, "notes": {}, "work": work["work"], "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12},
            "trace": {"busy_s": solves * (products_s + sketch_s + moments_s + rest_s), "top_ops": top}}


def test_the_benchmarks_trace_readers(one_device):
    """``pca_products_ms`` reads the operations that write a product's result
    (``f32[ell,rows]``, ``f32[ell,features]``), which the sketch's own
    passes (``f32[rows,ell]``, its Grams ``f32[ell,ell]``) do not; the share
    of the roofline takes the products a fit from the roots' ``passes``;
    ``pca_factor_ms`` reads the eigh custom calls by name, and not the
    sketch's ``ell x ell`` Grams beside them."""
    x = ht.array(_planted(999, 6, seed=1), split=0)
    _fit(x, 6, 2)
    telemetry.clear_spans()
    for _ in range(2):
        _fit(x, 6, 2)
    cfg = {"rows": 999, "features": FEATURES, "n_components": 6, "n_oversamples": 10, "iterated_power": 2}
    work = load_py("drivers", "pca_fit").work(cfg)
    assert work["product_shapes"] == ["f32[16,999]", f"f32[16,{FEATURES}]"]
    assert work["sketch_shapes"] == ["f32[999,16]", "f32[16,16]"] and work["moments_shapes"] == [f"(f32[{FEATURES}], f32[{FEATURES}])"]
    least = max(work["product_bytes"] / 819e9, work["product_operations"] / 197e12)
    shaped = {"rows": 999, "features": FEATURES, "ell": 16, "work": work}
    run = _trace_run(shaped, products_s=6 * least / 0.5, sketch_s=0.002, rest_s=0.001)
    assert load_py("layer_metrics", "pca_products_ms").read(run) == pytest.approx(1000 * 6 * least / 0.5)
    assert load_py("layer_metrics", "pca_products_roofline_pct").read(run) == pytest.approx(50.0)
    assert load_py("layer_metrics", "pca_factor_ms").read(run) == pytest.approx(1.0)
    assert run["notes"] == {"pca_products_a_solve": 6.0}
    # a program whose products are not among the top operations (an older one, or a trace it overran): nothing
    for metric in ("pca_products_ms", "pca_products_roofline_pct", "pca_factor_ms"):
        run = _trace_run(shaped, 0.1, 0.01, 0.01, ops=[["%fusion.1 fusion f32[999,16]", 0.3]])
        assert load_py("layer_metrics", metric).read(run) is None and metric in run["notes"]


# ------------------------------------------------------- the benchmark's yardstick
YARD_ROWS = 8191


@pytest.fixture(scope="module")
def driver():
    return load_py("drivers", "pca_fit")


@pytest.fixture(scope="module")
def cfg():
    from chipbench.run import load_json

    return load_json("configs", "pca-randomized-emb2048.json")


@pytest.fixture()
def state(driver, cfg, one_device):
    return driver.build(cfg, 3000000019, YARD_ROWS)


def test_the_drivers_table_is_the_configurations(driver, cfg, state):
    """The planted spectrum: a power law over every column, no gap after the
    components asked for, about means far off zero; seeded."""
    x = state["x"].numpy()
    assert x.shape == (YARD_ROWS, 2048) and x.dtype == np.float32 and state["x"].split == 0
    lo, hi = cfg["means"]
    assert lo - 0.01 <= x.mean(axis=0).min() and x.mean(axis=0).max() <= hi + 0.01
    sv = _exact(x, 258)["sv"] / np.sqrt(YARD_ROWS - 1)
    assert 8 < sv[0] / sv[255] < 32 and sv[255] / sv[256] < 1.05
    again = driver.build(cfg, 3000000019, YARD_ROWS)["x"].numpy()
    assert np.array_equal(again, x) and not np.array_equal(driver.build(cfg, 3000000020, YARD_ROWS)["x"].numpy(), x)


def test_the_drivers_sketch_is_the_estimators(driver, cfg):
    """The reference draws the sketch the estimator draws with
    ``random_state``: ``ht.random`` seeded with it, its first draw."""
    seed, f, ell = 3000000019, cfg["features"], cfg["n_components"] + cfg["n_oversamples"]
    ht.random.seed(seed)
    drawn = ht.random.randn(f, ell, dtype=ht.float32).numpy()
    np.testing.assert_array_equal(np.asarray(driver.sketch_of(seed, f, ell)), drawn)


def test_the_drivers_reference_is_the_randomized_pca_in_float64(driver, state):
    """The reference's steps (float32 products at ``highest`` a block of rows
    at a time, float64 sums and small factorizations) against the same steps
    in float64 numpy from the same sketch: float32 rounding apart."""
    x = state["x"].numpy().astype(np.float64)
    fit = state["fit"]
    mean = x.mean(axis=0)
    xc = x - mean
    q = np.asarray(driver.sketch_of(state["seed"], x.shape[1], fit["n_components"] + fit["n_oversamples"]), np.float64)
    for turn in range(fit["iterated_power"] + 1):
        b = np.linalg.qr(xc @ q)[0].T @ xc
        q = np.linalg.qr(b.T)[0]
    _, s, vt = np.linalg.svd(b, full_matrices=False)
    k = fit["n_components"]
    ref = driver.reference(state)
    np.testing.assert_allclose(ref["singular_values"], s[:k], rtol=2e-6)
    np.testing.assert_allclose(ref["mean"], mean, rtol=1e-7)
    v = ref["components"]
    assert np.linalg.norm(v - (v @ vt[:k].T) @ vt[:k], 2) < 1e-4


def test_the_program_is_correct_by_the_drivers_limits(driver, cfg, state):
    from chipbench.run import judge

    numbers = driver.compare(state, driver.solve(state), driver.reference(state))
    assert judge(numbers, cfg["limits"])[0], numbers


def test_the_one_pass_control_is_refused(driver, cfg, state):
    """Every product of the table in one bfloat16 pass, centred where it is
    read: the components that four power iterations leave unsettled move,
    and the subspace with them, past its limit."""
    from chipbench.run import judge

    numbers = driver.compare(state, driver.control(state), driver.reference(state))
    assert not judge(numbers, cfg["limits"])[0] and numbers["subspace_sine"] > cfg["limits"]["subspace_sine"], numbers


@pytest.mark.parametrize("fault", ["altered", "half"])
def test_a_fault_is_refused_by_sv_rel(driver, cfg, state, fault):
    from chipbench.control import planted

    ref = driver.reference(state)
    with planted(driver.faults()[fault]):
        numbers = driver.compare(state, driver.solve(state), ref)
    assert numbers["sv_rel"] > cfg["limits"]["sv_rel"], numbers
