"""KMeans.fit against a plain float32 Lloyd written here, and the arithmetic
of its assignment pinned (PR 27).

The fit's assignment is ``argmin_j |c_j|^2 - 2 x.c_j`` with the points rounded
to bfloat16 and the centers float32 in BOTH terms, on every backend
(``cluster/kmeans.py::_half_d2``).  Before PR 27 the product was left to the
backend's default: exact on the CPU, where these tests run, and on the MXU
both operands rounded to bfloat16 while ``|c|^2`` came from the float32
centers, which put every boundary off by ``x . (c - bf16(c))``.
"""

import itertools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.cluster import kmeans

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.run import load_py  # noqa: E402


def _bf16(a):
    """float32 values rounded to bfloat16, as float32."""
    return np.asarray(jnp.asarray(a, jnp.float32).astype(jnp.bfloat16).astype(jnp.float32))


def _plain_lloyd(x, centers, iters):
    """Exact Lloyd: float32 data, distances and means in float64."""
    x64, c = x.astype(np.float64), centers.astype(np.float64)
    for _ in range(iters + 1):  # the last pass only assigns
        d = ((x64[:, None, :] - c[None, :, :]) ** 2).sum(axis=2)
        labels = d.argmin(axis=1)
        final = c
        c = np.stack([x64[labels == j].mean(axis=0) if np.any(labels == j) else c[j] for j in range(len(c))])
        c = c.astype(np.float32).astype(np.float64)  # the fit carries float32 centers
    return final, labels, d.min(axis=1).sum()


def _blobs(seed, n, f, k, spread):
    rng = np.random.default_rng(seed)
    true = spread * rng.standard_normal((k, f))
    x = (true[rng.integers(0, k, n)] + rng.standard_normal((n, f))).astype(np.float32)
    init = (true + 0.25 * rng.standard_normal((k, f))).astype(np.float32)
    return x, init


@pytest.mark.parametrize("split", [None, 0])
@pytest.mark.parametrize("seed", [3, 11])
def test_fit_matches_plain_float32_lloyd(seed, split):
    """Seeded overlapping blobs (unit noise, centers N(0, 1) a coordinate,
    so neighbouring blobs share a few percent of their points), 10 iterations
    from the same initial centers.  Tolerances, with their reason: rounding a
    point to bfloat16 moves ``x.c`` by up to 2^-9 |x||c|, so the rows within
    that of a boundary, a few in a thousand here, may take the neighbour's
    label; one such row moves a center of n/k rows by about |x - c| k / n =
    1e-3 at this size, the flips fall on either side of a boundary alike, and
    ten iterations amplify them little on blobs this far apart: centers
    within 1e-2 (1.6e-3 to 7.0e-3 over six seeds, 2e-3 on these two), under
    1% of the labels differ (2e-4 to 1.2e-3), the inertia within 1e-3 of the
    plain fit's (1e-6 to 4e-5)."""
    n, f, k, iters = 13_001, 8, 4, 10  # 13,001 does not divide over the 8 test devices
    x, init = _blobs(seed, n, f, k, spread=1.0)
    want_c, want_labels, want_inertia = _plain_lloyd(x, init, iters)
    km = ht.cluster.KMeans(n_clusters=k, init=ht.array(init), max_iter=iters, tol=-1.0).fit(ht.array(x, split=split))
    assert km.n_iter_ == iters
    dist = np.linalg.norm(km.cluster_centers_.numpy().astype(np.float64) - want_c, axis=1).max()
    assert dist < 1e-2, dist
    assert np.mean(km.labels_.numpy() != want_labels) < 1e-2
    assert abs(km.inertia_ - want_inertia) / want_inertia < 1e-3


def _planted():
    """Two centers and a boundary point on which the arithmetic before PR 27
    errs, the witness the benchmark's KMeans driver probes a program with.
    ``c0``'s first coordinate rounds from 1.003 to 1.0 in bfloat16; the point
    is exact in bfloat16 and lies far along that coordinate, so the rounded
    cross term loses ``2 * 8 * 0.003`` against ``|c0|^2`` and hands the point
    to ``c1``."""
    driver = load_py("drivers", "kmeans_fit")
    return driver.WITNESS_X, driver.WITNESS_CENTERS


def test_planted_boundary_point_exposes_rounded_cross_term():
    """The witness is real: exactly, the planted point is nearest ``c0``;
    with the product's operands rounded and ``|c|^2`` from the unrounded
    centers (what the MXU's default made of the parent's program) it lands
    in cluster 1, and so it does with both terms from the rounded centers."""
    x, centers = _planted()
    assert np.array_equal(_bf16(x), x)  # rounding the points is not what decides it
    exact = ((x[:, None, :].astype(np.float64) - centers[None].astype(np.float64)) ** 2).sum(axis=2)
    assert exact[0].argmin() == 0
    c2 = (centers.astype(np.float64) ** 2).sum(axis=1)
    rounded = _bf16(centers).astype(np.float64)
    parent = c2[None, :] - 2.0 * x.astype(np.float64) @ rounded.T
    assert parent[0].argmin() == 1
    both_rounded = (rounded ** 2).sum(axis=1)[None, :] - 2.0 * x.astype(np.float64) @ rounded.T
    assert both_rounded[0].argmin() == 1


@pytest.mark.parametrize("through", ["step", "body", "fit"])
def test_planted_boundary_point_goes_to_its_nearest_center(through):
    """The program puts the planted point where the exact distances put it:
    in the final assignment (``_lloyd_step``), in the loop's body (a point
    wrongly given to ``c1`` would pull ``c1`` towards 8) and through the
    public ``fit``."""
    x, centers = _planted()
    n, k = x.shape[0], centers.shape[0]
    if through == "step":
        labels, *_ = kmeans._lloyd_step(jnp.asarray(x), jnp.asarray(centers), n, k)
        assert list(np.asarray(labels)) == [0, 0, 1, 0]
    elif through == "body":
        new, _ = kmeans._lloyd_body(jnp.asarray(x), jnp.asarray(centers), n, k)
        np.testing.assert_allclose(np.asarray(new), [x[[0, 1, 3]].mean(axis=0), x[2]], rtol=1e-6)
    else:
        km = ht.cluster.KMeans(n_clusters=k, init=ht.array(centers), max_iter=1, tol=-1.0).fit(ht.array(x, split=0))
        np.testing.assert_allclose(km.cluster_centers_.numpy(), [x[[0, 1, 3]].mean(axis=0), x[2]], rtol=1e-6)


def test_benchmark_driver_refuses_a_program_that_rounds_the_centers(monkeypatch):
    """The KMeans cell's driver probes the program with the witness before it
    makes its data: this program passes, and one whose cross term takes
    bfloat16 centers (the MXU's default before PR 27, written out here so
    that the CPU rounds too) exits with the reason."""
    driver = load_py("drivers", "kmeans_fit")
    driver._refuse_rounded_centers(ht)

    def rounded(xb, centers):
        xc = jnp.matmul(xb, centers.astype(jnp.bfloat16).T, preferred_element_type=jnp.float32)
        return jnp.sum(centers * centers, axis=1)[None, :] - 2.0 * xc

    monkeypatch.setattr(kmeans, "_half_d2", rounded)
    jax.clear_caches()  # the jitted Lloyd programs hold the traced `_half_d2`
    try:
        with pytest.raises(SystemExit, match="rounds the centers"):
            driver._refuse_rounded_centers(ht)
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("pad", [0, 3], ids=["every_row_real", "three_pad_rows"])
@pytest.mark.parametrize("which", ["body", "step"])
def test_lloyd_programs_make_one_product_of_the_data_each_way(which, pad):
    """The jaxpr of an iteration: the data is rounded to bfloat16 once, and
    takes part in two products, the assignment's (bfloat16 points against
    float32 centers, ``HIGH`` on the centers' side, float32 out) and the
    update's (a bfloat16 one-hot against the bfloat16 points, float32 out).
    In the loop's body the points carry a column of ones, the product's last
    column is the counts and nothing sums the one-hot in a reduction of its
    own; in one iteration alone (``_lloyd_step``) the product is of the
    points as they are and the counts are the one-hot's sum.  The precision
    is in the program, not left to the backend's default.  An iota over the
    rows (the row mask) exists only where rows are padded."""
    n, f, k = 4096, 16, 8
    xp, centers = jnp.zeros((n, f), jnp.float32), jnp.zeros((k, f), jnp.float32)
    fn = kmeans._lloyd_step.__wrapped__ if which == "step" else kmeans._lloyd_body
    eqns = list(_equations(jax.make_jaxpr(lambda a, b: fn(a, b, n - pad, k))(xp, centers).jaxpr))
    narrowed = [e for e in eqns if e.primitive.name == "convert_element_type"
                and e.invars[0].aval.shape == (n, f) and e.outvars[0].aval.dtype == jnp.bfloat16]
    assert len(narrowed) == 1
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assign, update = dots
    assert [v.aval.dtype for v in assign.invars] == [jnp.bfloat16, jnp.float32]
    assert [v.aval.shape for v in assign.invars] == [(n, f), (k, f)]
    assert tuple(assign.params["precision"]) == (jax.lax.Precision.DEFAULT, jax.lax.Precision.HIGH)
    assert [v.aval.dtype for v in update.invars] == [jnp.bfloat16, jnp.bfloat16]
    assert [v.aval.shape for v in update.invars] == [(n, k), (n, f + 1 if which == "body" else f)]
    assert all(e.params["preferred_element_type"] == jnp.float32 for e in dots)
    one_hot_sums = [e for e in eqns if e.primitive.name == "reduce_sum" and e.invars[0].aval.shape == (n, k)]
    assert len(one_hot_sums) == (0 if which == "body" else 1)
    row_masks = [e for e in eqns if e.primitive.name == "iota" and e.outvars[0].aval.shape == (n,)]
    assert len(row_masks) == (0 if not pad else 2 if which == "step" else 1)  # the step masks its inertia too


_cluster_means = jax.jit(kmeans._cluster_means, static_argnums=(3, 4, 5))  # static in every caller


def _means_case(f, pad, n_true=20_001, k=5):
    """Points around 3 (so that a mean is far from 0 and a relative tolerance
    means something), labels that leave the last cluster empty, and ``pad``
    rows after the real ones that are NOT zero and carry a real cluster's
    label, as a pending elementwise chain can leave them."""
    rng = np.random.default_rng(100 * f + pad)
    x = (3.0 + rng.standard_normal((n_true + pad, f))).astype(np.float32)
    labels = rng.integers(0, k - 1, n_true + pad).astype(np.int32)
    x[n_true:], labels[n_true:] = 7.5, 2
    centers = rng.standard_normal((k, f)).astype(np.float32)
    counts = np.bincount(labels[:n_true], minlength=k)
    onehot = np.eye(k)[labels[:n_true]]
    sums = onehot.T @ _bf16(x[:n_true]).astype(np.float64)
    want = np.where(counts[:, None] > 0, sums / np.maximum(counts, 1)[:, None], centers)
    return x, labels, centers, counts, want


@pytest.mark.parametrize("resident", [True, False], ids=["column_of_ones", "one_hot_summed"])
@pytest.mark.parametrize("pad", [0, 5], ids=["every_row_real", "pad_rows_filled"])
@pytest.mark.parametrize("f", [3, 16, 17])
def test_cluster_means_sums_and_counts(f, pad, resident):
    """Both forms of the update (the fit loop's one product with a column of
    ones, and one iteration's product and sum) against a float64 ``onehot.T @ bf16(x)``
    over ``np.bincount``'s counts.  A cluster holds some 5,000 rows here, so a
    count off by one moves its mean by 2e-4 of itself, 200 times the
    tolerance: the counts are ``np.bincount``'s.  The empty cluster keeps its
    center to the bit and filled pad rows change nothing."""
    x, labels, centers, counts, want = _means_case(f, pad)
    n_true, k = x.shape[0] - pad, centers.shape[0]
    assert counts[-1] == 0 and counts[:-1].min() > 4000
    new, shift = _cluster_means(
        jnp.asarray(x).astype(jnp.bfloat16), jnp.asarray(labels), jnp.asarray(centers), n_true, k, resident)
    new = np.asarray(new)
    np.testing.assert_allclose(new[:-1], want[:-1], rtol=1e-6)
    assert np.array_equal(new[-1], centers[-1])
    np.testing.assert_allclose(float(shift), ((want - centers) ** 2).sum(), rtol=1e-5)


@pytest.mark.parametrize("resident", [True, False], ids=["column_of_ones", "one_hot_summed"])
def test_cluster_means_over_four_devices_matches_one_device(resident):
    """Rows split over a four-device mesh, three of them padding (20,001 rows
    do not divide by four): GSPMD's psum of the products gives the one-device
    means, so the counts crossed the devices exactly."""
    from heat_tpu.parallel.comm import Communication

    f, pad = 16, 3
    x, labels, centers, _, want = _means_case(f, pad)
    n_true, k = x.shape[0] - pad, centers.shape[0]
    comm = Communication(jax.devices()[:4])
    assert comm.size == 4
    xb = jnp.asarray(x).astype(jnp.bfloat16)
    one, one_shift = _cluster_means(xb, jnp.asarray(labels), jnp.asarray(centers), n_true, k, resident)
    four, four_shift = _cluster_means(
        jax.device_put(xb, comm.sharding(0)), jax.device_put(jnp.asarray(labels), comm.sharding(0)),
        jax.device_put(jnp.asarray(centers), comm.sharding(None)), n_true, k, resident)
    assert len(four.sharding.device_set) == 4
    np.testing.assert_allclose(np.asarray(four), np.asarray(one), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(four)[:-1], want[:-1], rtol=1e-6)
    assert np.array_equal(np.asarray(four)[-1], centers[-1])
    np.testing.assert_allclose(float(four_shift), float(one_shift), rtol=1e-5)


# ---------------------------------------------------------------------------
# One iteration against NumPy over a sweep of shapes (PR 29).  The sweep and
# the three properties under it (an empty cluster, filled pad rows, rows over
# the mesh) stood in tests/test_kernels.py against the Pallas Lloyd kernel;
# they are properties of Lloyd, so they moved here with `_numpy_lloyd` when the
# kernel went, and now hold the two programs that are left.

EPS = float(np.finfo(np.float32).eps)


def _means(x, c, lbl):
    """Per-cluster means of float64 rows; an empty cluster keeps its center."""
    return np.stack([x[lbl == j].mean(0) if (lbl == j).any() else c[j] for j in range(c.shape[0])])


def _numpy_lloyd(x, c):
    """One Lloyd iteration in float64: every row's squared distance to every
    center, and the new centers."""
    x, c = x.astype(np.float64), c.astype(np.float64)
    d = ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1)
    return d, _means(x, c, d.argmin(1))


def _one_iteration(through, xp, centers, n_true):
    """(new centers, labels or None, inertia or None) of one iteration of the
    program: the final pass, or the fit loop held to one iteration."""
    k = centers.shape[0]
    if through == "step":
        labels, new, _, inertia = kmeans._lloyd_step(jnp.asarray(xp), jnp.asarray(centers), n_true, k)
        return np.asarray(new), np.asarray(labels)[:n_true], float(inertia)
    new, n_iter, _ = kmeans._lloyd_loop(jnp.asarray(xp), jnp.asarray(centers), n_true, k, 1, -1.0)
    assert int(n_iter) == 1
    return np.asarray(new), None, None


def _check_against_numpy(x, centers, new=None, labels=None, inertia=None):
    """The program's stated arithmetic is exact Lloyd on the points rounded to
    bfloat16 (the centers stay float32), so NumPy gets the rounded points and
    every tolerance is float32's rounding of that arithmetic, derived here:

    * a term ``|c|^2 - 2 xb.c`` is ``f`` products and ``f + 1`` additions: off
      by at most ``(f + 2) eps (|c|^2 + 2 sum_l |xb_l c_l|)``.  A row whose two
      nearest centers lie further apart than twice that is decided and carries
      NumPy's label; any other row may carry either of the two, so without the
      program's labels (the loop returns none) the new centers have to be the
      means under one of those assignments: a handful of rows, enumerated.
    * a mean is a float32 sum of at most ``n`` exact bfloat16 values; its
      rounding errors add as a random walk (Higham and Mary 2019: sqrt(n) u
      for n u), so a center lies within ``4 sqrt(n) eps max|xb|``: 6e-5 at
      1,003 rows, where a row in the wrong cluster moves a center by 1e-2.
    * `_lloyd_step` takes ``|x|^2`` from the UNROUNDED points and the
      assignment's minimum from the rounded ones, so its inertia is NumPy's on
      the rounded points plus ``sum |x|^2 - |xb|^2`` (up to 2^-8 of
      ``sum |x|^2``), to the rounding of each row's terms and of the float32 sum
      over the rows: ``(f + 2 + 4 sqrt(n)) eps sum (|x| + max|c|)^2``."""
    n, f = x.shape
    xb, c = _bf16(x).astype(np.float64), centers.astype(np.float64)
    d, _ = _numpy_lloyd(xb, c)
    two = np.argsort(d, axis=1)[:, :2]
    gap = np.diff(np.take_along_axis(d, two, axis=1), axis=1)[:, 0]
    slack = (f + 2) * EPS * ((c * c).sum(axis=1)[None, :] + 2 * np.abs(xb) @ np.abs(c).T).max(axis=1)
    open_rows = np.flatnonzero(gap <= 2 * slack)
    want_labels = two[:, 0].copy()
    if labels is not None:
        assert np.all((labels == two[:, 0]) | (labels == two[:, 1]))
        assert np.array_equal(np.delete(labels, open_rows), np.delete(want_labels, open_rows))
        candidates = [labels]
    else:
        assert len(open_rows) <= 10, len(open_rows)
        candidates = []
        for pick in itertools.product((0, 1), repeat=len(open_rows)):
            want_labels[open_rows] = two[open_rows, list(pick)]
            candidates.append(want_labels.copy())
    if new is not None:
        off = min(np.abs(new - _means(xb, c, lbl)).max() for lbl in candidates)
        assert off <= 4 * np.sqrt(n) * EPS * np.abs(xb).max(), off
    if inertia is not None:
        x64 = x.astype(np.float64)
        want = d.min(axis=1).sum() + (x64 ** 2).sum() - (xb ** 2).sum()
        size = (np.linalg.norm(x64, axis=1) + np.linalg.norm(c, axis=1).max()) ** 2
        assert abs(inertia - want) <= (f + 2 + 4 * np.sqrt(n)) * EPS * size.sum(), (inertia, want)


def _padded(x, fill=0.0):
    """Rows padded to a multiple of 32 (the mesh's quantum), pad rows ``fill``."""
    xp = np.full((-(-x.shape[0] // 32) * 32, x.shape[1]), fill, np.float32)
    xp[: x.shape[0]] = x
    return xp


# the shapes the kernel's two sweeps used: f from 1 to 128, k from 2 to 16 and
# not a power of two, n off the padding quantum; the last is 40,000 rows of 64
SHAPES = [(1003, 16, 8), (517, 8, 5), (130, 4, 7), (999, 16, 12), (96, 128, 8), (64, 64, 2),
          (517, 128, 4), (517, 128, 13), (517, 64, 2), (517, 32, 8), (517, 16, 3), (517, 8, 9),
          (517, 4, 16), (517, 2, 2), (517, 1, 4), (40_000, 64, 3)]


@pytest.mark.parametrize("through", ["step", "loop"])
@pytest.mark.parametrize("n,f,k", SHAPES, ids=["x".join(map(str, s)) for s in SHAPES])
def test_one_iteration_matches_numpy_lloyd(n, f, k, through):
    """Labels, new centers and inertia of the final pass, and the new centers
    of the fit loop's one iteration, against NumPy over the sweep."""
    rng = np.random.default_rng(1000 * f + k)
    x = rng.standard_normal((n, f)).astype(np.float32)
    centers = rng.standard_normal((k, f)).astype(np.float32)
    new, labels, inertia = _one_iteration(through, _padded(x), centers, n)
    _check_against_numpy(x, centers, new, labels, inertia)


@pytest.mark.parametrize("through", ["step", "loop"])
def test_empty_cluster_keeps_its_center(through):
    """A cluster that takes no point keeps its center to the bit (the
    ``where`` guard of `_cluster_means`), and nothing is NaN."""
    x = np.zeros((64, 16), np.float32)  # every point at the origin
    centers = np.stack([np.zeros(16), np.full(16, 100.0)]).astype(np.float32)
    new, labels, inertia = _one_iteration(through, x, centers, 64)
    assert not np.isnan(new).any() and (inertia is None or inertia == 0.0)
    assert np.array_equal(new[1], centers[1])
    _check_against_numpy(x, centers, new, labels, inertia)


@pytest.mark.parametrize("through", ["step", "loop"])
def test_filled_pad_rows_contribute_nothing(through):
    """40 real rows in a 64-row buffer whose pad rows hold 1e6, not zero:
    sums, counts and inertia are those of the 40 rows alone (the row mask)."""
    rng = np.random.default_rng(9)
    n, f, k = 40, 16, 5
    x = rng.standard_normal((n, f)).astype(np.float32)
    centers = rng.standard_normal((k, f)).astype(np.float32)
    new, labels, inertia = _one_iteration(through, _padded(x, fill=1e6), centers, n)
    _check_against_numpy(x, centers, new, labels, inertia)


def test_one_iteration_of_fit_over_the_mesh_matches_numpy_lloyd():
    """1,003 rows split over the 8 test devices (uneven: the last shard is
    padded), one iteration of the public ``fit``: the centers are NumPy's
    after one iteration, the labels and the inertia its assignment against
    those centers, so the psum of sums, counts and inertia crossed the
    devices whole."""
    n, f, k = 1003, 16, 8
    rng = np.random.default_rng(1)
    x = rng.standard_normal((n, f)).astype(np.float32)
    centers = rng.standard_normal((k, f)).astype(np.float32)
    hx = ht.array(x, split=0)
    assert hx.comm.size == 8 and hx.larray_padded.shape[0] > n
    km = ht.cluster.KMeans(n_clusters=k, init=ht.array(centers), max_iter=1, tol=-1.0).fit(hx)
    assert km.n_iter_ == 1
    new = km.cluster_centers_.numpy()
    _check_against_numpy(x, centers, new)
    _check_against_numpy(x, new, labels=km.labels_.numpy(), inertia=km.inertia_)  # the final pass moves no center
