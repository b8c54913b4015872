"""``KMedians.fit`` against a plain reference (PR 37).

Three layers, each held to the one under it: a KMedians written here in
``numpy`` (float32 Manhattan distances added left to right, the first center
on ties, float64 medians of the float32 members, an empty cluster keeping its
center), the benchmark's plain reference
(``chipbench/drivers/kmedians_fit.py``, which imports nothing of the
program), and the program: one assignment and one grouped exact selection an
iteration, on one device and over the mesh, where the rows are padded.
"""

import os
import sys

import jax
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.parallel.comm import Communication

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.run import judge, load_json, load_py  # noqa: E402
from chipbench.control import planted  # noqa: E402


def _l1(a, c):
    """(rows, clusters) Manhattan distances in the points' precision, the
    columns' magnitudes added left to right."""
    d = np.abs(a[:, None, 0] - c[None, :, 0])
    for i in range(1, a.shape[1]):
        d = d + np.abs(a[:, None, i] - c[None, :, i])
    return d


def np_kmedians(a, c0, max_iter, tol=-1.0):
    """(centers, labels, inertia, n_iter) of plain KMedians from ``c0``."""
    c = c0.astype(a.dtype)
    n_iter = 0
    while n_iter < max_iter:
        labels = _l1(a, c).argmin(axis=1)
        new = c.copy()
        for j in range(len(c)):
            if np.any(labels == j):
                new[j] = np.median(a[labels == j].astype(np.float64), axis=0).astype(a.dtype)
        shift = np.float32(((new - c) ** 2).sum())
        c, n_iter = new, n_iter + 1
        if not shift > tol:
            break
    d = _l1(a, c)
    return c, d.argmin(axis=1), float((d.min(axis=1).astype(np.float64) ** 2).sum()), n_iter


def _blobs(rows, kind="plain", dtype=np.float32, features=3):
    """Four overlapping blobs, so that the labels move for several iterations."""
    rng = np.random.default_rng(11)
    a = (rng.standard_normal((rows, features)) + 1.5 * rng.integers(0, 4, (rows, 1)) * [1, -1, 0.5, 1][:features]).astype(dtype)
    if kind == "ties":  # whole numbers: many members share the middle
        a = np.round(a)
    return a


def _start(a, k=4):
    return a[np.random.default_rng(5).choice(len(a), k, replace=False)].copy()


@pytest.fixture()
def one_device():
    ht.use_comm(Communication(jax.devices()[:1]))
    try:
        yield
    finally:
        ht.use_comm(ht.WORLD)


@pytest.fixture()
def four_devices():
    ht.use_comm(Communication(jax.devices()[:4]))
    try:
        yield
    finally:
        ht.use_comm(ht.WORLD)


def _check(km, a, c0, max_iter, tol=-1.0):
    centers, labels, inertia, n_iter = np_kmedians(a, c0, max_iter, tol)
    got = km.cluster_centers_.numpy()
    assert got.dtype == centers.dtype and np.array_equal(got, centers, equal_nan=True)  # medians bit for bit
    assert km.labels_.shape == (len(a),) and np.array_equal(km.labels_.numpy(), labels)
    assert km.n_iter_ == n_iter
    np.testing.assert_allclose(km.inertia_, inertia, rtol=2e-6)
    return centers


@pytest.mark.parametrize("split", [None, 0])
@pytest.mark.parametrize("rows", [1003, 1000, 64])
@pytest.mark.parametrize("kind", ["plain", "ties"])
def test_fit_is_the_plain_kmedians(kind, rows, split):
    """Over the suite's eight devices: 1003 rows are padded, 1000 divide; the
    clusters' counts come odd and even; ``ties`` rounds the points so that the
    middle members coincide."""
    a = _blobs(rows, kind)
    c0 = _start(a)
    km = ht.cluster.KMedians(n_clusters=4, init=ht.array(c0), max_iter=6, tol=-1.0).fit(ht.array(a, split=split))
    _check(km, a, c0, 6)
    counts = np.bincount(km.labels_.numpy(), minlength=4)
    assert rows == 64 or {int(c) % 2 for c in counts} == {0, 1} or kind == "ties", counts


@pytest.mark.parametrize("mesh", ["one_device", "four_devices"])
def test_fit_on_other_meshes(mesh, request):
    request.getfixturevalue(mesh)
    a = _blobs(1003)
    c0 = _start(a)
    km = ht.cluster.KMedians(n_clusters=4, init=ht.array(c0), max_iter=5, tol=-1.0).fit(ht.array(a, split=0))
    _check(km, a, c0, 5)


@pytest.mark.parametrize("split", [None, 0, 1])
def test_a_cluster_that_empties_keeps_its_center(split):
    a = _blobs(501)
    c0 = _start(a)
    c0[2] = [40.0, 40.0, -40.0]  # nearest to no point
    km = ht.cluster.KMedians(n_clusters=4, init=ht.array(c0), max_iter=4, tol=-1.0).fit(ht.array(a, split=split))
    centers = _check(km, a, c0, 4)
    assert np.array_equal(centers[2], c0[2]) and 2 not in km.labels_.numpy()


@pytest.mark.parametrize("split", [None, 0])
def test_a_nan_in_a_member_is_numpys(split):
    """The row that holds a NaN compares under nothing and stays with the
    first center (``argmin``); that cluster's median of that column is NaN,
    as ``np.median`` has it, and the other columns and clusters are exact."""
    a = _blobs(401)
    a[17, 1] = np.nan
    c0 = _start(a)
    km = ht.cluster.KMedians(n_clusters=4, init=ht.array(c0), max_iter=1, tol=-1.0).fit(ht.array(a, split=split))
    got = km.cluster_centers_.numpy()
    centers, _, _, _ = np_kmedians(a, c0, 1)
    assert np.isnan(got[0, 1]) and np.isnan(got).sum() == 1
    assert np.array_equal(got, centers, equal_nan=True)


@pytest.mark.parametrize("init", ["random", "kmedians++", "probability_based"])
def test_seeded_inits_and_the_convergence_test(init):
    """From the library's own seeding, until the shift falls to ``tol``: the
    same centers, labels and iteration count as the plain loop from the same
    start."""
    a = _blobs(700)
    x = ht.array(a, split=0)
    km = ht.cluster.KMedians(n_clusters=4, init=init, random_state=3, max_iter=50, tol=1e-4)
    km._initialize_cluster_centers(x)
    c0 = km.cluster_centers_.numpy()
    assert c0.shape == (4, 3) and all(any(np.array_equal(c, row) for row in a) for c in c0)
    km.fit(x)
    _check(km, a, c0, 50, tol=1e-4)
    assert 1 < km.n_iter_ < 50


@pytest.mark.parametrize("dtype,features", [("float64", 3), ("float32", 1), ("float32", 7), ("int32", 3)])
def test_other_types_and_widths(dtype, features):
    a = _blobs(333, dtype=np.float64 if dtype == "float64" else np.float32, features=min(features, 4))
    if features > a.shape[1]:
        a = np.concatenate([a, a[:, : features - a.shape[1]] * 0.5], axis=1)
    if dtype == "int32":
        a = np.round(a * 4).astype(np.int32)
    c0 = _start(a).astype(np.float64 if dtype == "float64" else np.float32)
    km = ht.cluster.KMedians(n_clusters=4, init=ht.array(c0), max_iter=4, tol=-1.0).fit(ht.array(a, split=0))
    _check(km, a.astype(c0.dtype), c0, 4)


def test_a_resumable_fit_runs_the_same_iterations(tmp_path):
    a = _blobs(600)
    c0 = _start(a)
    km = ht.cluster.KMedians(n_clusters=4, init=ht.array(c0), max_iter=6, tol=-1.0,
                             checkpoint_every=2, checkpoint_dir=str(tmp_path)).fit(ht.array(a, split=0))
    _check(km, a, c0, 6)


def test_predict_labels_as_fit_labelled():
    a = _blobs(300)
    x = ht.array(a, split=0)
    km = ht.cluster.KMedians(n_clusters=4, init=ht.array(_start(a)), max_iter=5, tol=-1.0).fit(x)
    assert np.array_equal(km.predict(x).numpy().ravel(), km.labels_.numpy())


# ------------------------------------------------------- the benchmark's yardstick
ROWS = 16384


@pytest.fixture(scope="module")
def driver():
    return load_py("drivers", "kmedians_fit")


@pytest.fixture(scope="module")
def cfg():
    return load_json("configs", "kmedians-spheres3d.json")


@pytest.fixture()
def state(driver, cfg, one_device):
    return driver.build(cfg, 3000000019, ROWS)


def test_the_drivers_table_is_upstreams_spheres(driver, cfg, state):
    a = state["x"].numpy()
    assert a.shape == (ROWS, 3) and a.dtype == np.float32 and state["x"].split == 0
    for j, block in enumerate(np.split(a, 4)):
        np.testing.assert_allclose(block.mean(axis=0), 4.0 * driver.SPHERES[j], atol=0.06)
        np.testing.assert_allclose(block.std(axis=0), 1.0, atol=0.04)
    again = driver.build(cfg, 3000000019, ROWS)
    assert np.array_equal(again["x"].numpy(), a) and np.array_equal(again["p"]["init"], state["p"]["init"])
    assert not np.array_equal(driver.build(cfg, 3000000020, ROWS)["x"].numpy(), a)


def test_the_drivers_reference_is_the_plain_kmedians(driver, state):
    a = state["x"].numpy()
    ref = driver.reference(state)
    centers, labels, inertia, n_iter = np_kmedians(a, state["p"]["init"], state["max_iter"])
    assert np.array_equal(ref["centers"], centers) and np.array_equal(np.asarray(ref["labels"]), labels)
    assert ref["n_iter"] == n_iter == 5
    np.testing.assert_allclose(ref["inertia"], inertia, rtol=1e-6)


def test_the_program_is_correct_by_the_drivers_limits(driver, cfg, state):
    out = driver.solve(state)
    numbers = driver.compare(state, out, driver.reference(state))
    assert judge(numbers, cfg["limits"])[0], numbers
    assert numbers["centers_dist"] == 0.0 and numbers["labels_off_share"] == 0.0 and numbers["n_iter_gap"] == 0


def test_the_control_is_refused(driver, cfg, state):
    numbers = driver.compare(state, driver.control(state), driver.reference(state))
    assert not judge(numbers, cfg["limits"])[0] and numbers["centers_dist"] > cfg["limits"]["centers_dist"], numbers


@pytest.mark.parametrize("fault,by", [("half", "centers_dist"), ("unmasked", "centers_dist"), ("altered", "centers_dist"),
                                      ("altered_final", "labels_off_share")])
def test_a_fault_is_refused_by_its_number(driver, cfg, state, fault, by):
    ref = driver.reference(state)
    with planted(driver.faults()[fault]):
        numbers = driver.compare(state, driver.solve(state), ref)
    assert numbers[by] > cfg["limits"][by], numbers
    if fault == "altered_final":
        assert numbers["inertia_rel"] > cfg["limits"]["inertia_rel"], numbers


def test_the_work_model(driver, cfg):
    n, f, k, it = 2 ** 28, 3, 4, 5
    assert cfg["rows"] == n and (cfg["features"], cfg["clusters"], cfg["max_iter"]) == (f, k, it)
    work = driver.work(cfg)
    assert (work["count_pass_bytes"], work["count_pass_operations"]) == (n * f * 4 + n * 4, 2 * n * f)
    assert {key: work[key] for key in ("bytes", "operations")} == {"bytes": it * (2 * n * f * 4 + 2 * n * 4) + n * f * 4 + n * 4,
                                "operations": (it + 1) * 3 * k * f * n}
    assert driver.work(cfg)["bytes"] == 47_244_640_256


def test_the_witness_refuses_a_loop_that_sorts(driver, one_device, monkeypatch):
    """What the driver does to the program before PR 37: a fit loop whose
    update sorts exits with the reason before anything of the cell's size is
    made."""
    import jax.numpy as jnp

    from heat_tpu.cluster import kmedians

    def sorting(xp, centers, **kw):
        return centers + jnp.sort(xp, axis=0)[:2].sum(), jnp.int32(1), jnp.float32(0)

    monkeypatch.setattr(kmedians, "_kmedians_loop", sorting)
    with pytest.raises(SystemExit, match="sorting a masked copy"):
        driver._refuse_sorting_update(ht)
    monkeypatch.undo()
    driver._refuse_sorting_update(ht)  # the program as it stands passes
