"""``Lasso.fit`` against a plain reference (PR 39).

Three layers, each held to the one under it: cyclic coordinate descent
written here in float64 ``numpy`` as it is defined (the whole residual made
again for every coordinate), the benchmark's plain reference
(``chipbench/drivers/lasso_fit.py``, which imports nothing of the program and
descends on the normal equations), and the program: the covariance form on a
tall table and the residual form on a wide one, on one device and over the
mesh, where the rows are padded.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.parallel.comm import Communication
from heat_tpu.regression import lasso

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.run import judge, load_json, load_py  # noqa: E402
from chipbench.control import planted  # noqa: E402


def np_lasso(a, y, lam, max_iter, tol=-1.0):
    """(theta, sweeps) of the definition: for every coordinate the residual
    of all the others, its product with the column, the soft threshold (none
    for the intercept, coordinate 0) over the column's sum of squares."""
    X = np.concatenate([np.ones((a.shape[0], 1)), a.astype(np.float64)], axis=1)
    y = y.astype(np.float64).ravel()
    theta, col_sq = np.zeros(X.shape[1]), (X * X).sum(axis=0)
    for sweep in range(max_iter):
        old = theta.copy()
        for j in range(X.shape[1]):
            rho = X[:, j] @ (y - X @ theta + X[:, j] * theta[j])
            theta[j] = (rho if j == 0 else np.sign(rho) * max(abs(rho) - lam, 0.0)) / col_sq[j]
        if not np.max(np.abs(theta - old)) >= tol:
            return theta, sweep + 1
    return theta, max_iter


def _problem(rows, features, seed=7):
    """Correlated columns, every fourth off zero, a third of them in the model."""
    rng = np.random.default_rng(seed)
    z = rng.standard_normal((rows, features + 1))
    a = (z[:, 1:] + 0.5 * z[:, :-1] + 0.3 * (np.arange(features) % 4 == 3)).astype(np.float32)
    w = np.where(np.arange(features) % 3 == 0, rng.uniform(0.25, 2.0, features) * rng.choice([-1, 1], features), 0.0)
    y = (a @ w + 0.5 + 0.5 * rng.standard_normal(rows)).astype(np.float32)
    return a, y[:, None]


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


# ------------------------------------------------------------ the program
@pytest.mark.parametrize("split", [None, 0])
@pytest.mark.parametrize("rows, features, form", [(301, 5, "gram"), (1003, 50, "gram"), (4801, 128, "gram"),
                                                  (37, 50, "residual")])
def test_fit_is_the_definition_when_max_iter_ends_it(four_devices, rows, features, form, split):
    """Widths that are and are not lane-aligned, rows no four devices divide."""
    a, y = _problem(rows, features)
    assert lasso._gram_form(rows, features, np.float32) == (form == "gram")
    want, sweeps = np_lasso(a, y, 0.05 * rows, 12)
    model = ht.regression.Lasso(lam=0.05 * rows, max_iter=12, tol=-1.0).fit(ht.array(a, split=split), ht.array(y, split=split))
    got = model.theta.numpy().ravel()
    assert model.n_iter == sweeps == 12
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert np.array_equal(got[1:] != 0, want[1:] != 0) and 0 < np.count_nonzero(got[1:]) < features
    assert model.coef_.shape == (features, 1) and model.intercept_.numpy().item() == got[0]


@pytest.mark.parametrize("split", [None, 0])
@pytest.mark.parametrize("rows, features", [(301, 5), (1003, 50), (37, 50)])
def test_fit_is_the_definition_when_tol_ends_it(four_devices, rows, features, split):
    a, y = _problem(rows, features)
    want, sweeps = np_lasso(a, y, 0.05 * rows, 500, tol=1e-4)
    model = ht.regression.Lasso(lam=0.05 * rows, max_iter=500, tol=1e-4).fit(ht.array(a, split=split), ht.array(y, split=split))
    assert 1 < sweeps < 500 and abs(model.n_iter - sweeps) <= 1  # a move within rounding of tol may end it a sweep apart
    np.testing.assert_allclose(model.theta.numpy().ravel(), want, atol=2e-4)


def test_fit_through_the_gram_kernel_on_one_device(one_device):
    """One device, 128 columns, more rows than a tile of ``gram_syrk``: the
    Gram through the kernel (interpreted here), its diagonal from the
    moments' pass, the rows past the last tile and the last block."""
    a, y = _problem(20000, 128)
    want, _ = np_lasso(a, y, 1000.0, 6)
    got = ht.regression.Lasso(lam=1000.0, max_iter=6, tol=-1.0).fit(ht.array(a, split=0), ht.array(y, split=0)).theta.numpy()
    np.testing.assert_allclose(got.ravel(), want, atol=2e-6)


def _shifted_equations(a, y, c, cy):
    """`lasso._normal_equations`' result in float64, for the shift it chose."""
    n, f = a.shape
    X = np.concatenate([np.ones((n, 1)), a.astype(np.float64) - c], axis=1)
    G, b = X.T @ X, X.T @ (y.astype(np.float64) - cy)
    drag = np.concatenate([[0.0], c])
    col_sq = np.concatenate([[n], (a.astype(np.float64) ** 2).sum(axis=0)])
    return G + drag[:, None] * G[0][None, :], b + drag * b[0], col_sq, drag


@pytest.mark.parametrize("off_zero", [0.0, 50.0], ids=["about_zero", "50_spreads_off"])
def test_the_normal_equations_stand_in_a_shifted_frame(one_device, off_zero):
    """`lasso._normal_equations` through the kernel: the shift is the first
    4,096 rows' means, the numbers are of the size of the columns' SPREADS
    wherever the columns stand, the columns' sums of squares are those of the
    table as it lies, and the Gram's diagonal is the moments' (the kernel's
    three-term product reads a diagonal 2.76e-6 low)."""
    a, y = _off_zero_problem(20000, 128, off_zero, 7) if off_zero else _problem(20000, 128)
    A, b, col_sq, drag, cy = jax.jit(lambda x, y: lasso._normal_equations(x, y, 20000, True, lasso._own))(jnp.asarray(a), jnp.asarray(y[:, 0]))
    np.testing.assert_allclose(drag[1:], a[:4096].mean(axis=0), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(cy, y[:4096].mean(), rtol=1e-5)
    wA, wb, wsq, wdrag = _shifted_equations(a, y[:, 0], np.asarray(drag[1:], np.float64), float(cy))
    np.testing.assert_allclose(col_sq, wsq, rtol=3e-7)
    np.testing.assert_allclose(np.diag(A)[1:], np.diag(wA)[1:], rtol=1e-6)  # the kernel's own would read 2.76e-6 low
    np.testing.assert_allclose(A[1:, 1:], wA[1:, 1:], rtol=0, atol=3e-6 * 20000)
    np.testing.assert_allclose(A[:, 0], wA[:, 0], rtol=2e-6, atol=2e-6 * 20000)
    np.testing.assert_allclose(A[0], wA[0], rtol=0, atol=2e-6 * 20000)
    np.testing.assert_allclose(b, wb, rtol=0, atol=2e-6 * 20000 * max(1.0, np.abs(y).max() / 10))


def _float64_descent(G, b, lams, max_iter, tol=-1.0):
    theta = np.zeros(b.shape[0])
    for sweep in range(max_iter):
        old = theta.copy()
        for j in range(b.shape[0]):
            rho = b[j] - G[j] @ theta + G[j, j] * theta[j]
            theta[j] = np.sign(rho) * max(abs(rho) - lams[j], 0.0) / G[j, j]
        if not np.max(np.abs(theta - old)) >= tol:
            return theta, sweep + 1
    return theta, max_iter


@pytest.mark.parametrize("shifted", [False, True], ids=["plain", "dragged"])
@pytest.mark.parametrize("features, tol", [(5, -1.0), (128, -1.0), (130, -1.0), (50, 1e-3)])
def test_the_descent_on_the_normal_equations(features, tol, shifted):
    """The kernel alone (interpreted here) against float64 sweeps on the
    table's own ``G`` and ``b``: plain (``A = G``, no drag) and dragged (the
    equations about a shift of half a spread and more, the intercept's place
    holding ``u``; what a sweep moved is read of the intercept itself, so
    ``tol`` ends both after the same sweeps); 129 coordinates fill a row of
    registers and one more, 131 spill into the next row."""
    from heat_tpu.core import kernels

    a, y = _problem(2000, features)
    a = a + 0.5 * np.arange(features, dtype=np.float32)  # columns that stand off zero
    X = np.concatenate([np.ones((2000, 1)), a.astype(np.float64)], axis=1)
    G, b = X.T @ X, X.T @ y[:, 0].astype(np.float64)
    lams = np.full(features + 1, 100.0)
    lams[0] = 0.0
    want, sweeps = _float64_descent(G, b, lams, 40, tol)
    c, cy = (a.astype(np.float64).mean(axis=0), float(y.mean())) if shifted else (np.zeros(features), 0.0)
    A, bb, col_sq, drag = _shifted_equations(a, y[:, 0], c, cy)
    assert kernels.cd_supported(features + 1, np.float32) and not kernels.cd_supported(features + 1, np.float64)
    made = tuple(jnp.asarray(v, jnp.float32) for v in (A, bb, col_sq, drag, cy))
    got, it, moved = jax.jit(lambda made: (lambda t, it, moved: (lasso._leave(t, made, True), it, moved))(
        *lasso._descend_gram(*made, 100.0, tol, lasso._enter(jnp.zeros((features + 1,), jnp.float32), made, True), 40)))(made)
    assert abs(int(it) - sweeps) <= (tol > 0) and (tol > 0 or float(moved) >= 0)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=3e-5 if not shifted else 3e-6)
    assert np.array_equal(np.asarray(got)[1:] != 0, want[1:] != 0)


def _off_zero_problem(rows, features, ratio, seed):
    """`_problem`'s correlated columns at unit second moment, two of them with
    a mean ``ratio`` spreads off zero (a Kelvin temperature) and a coefficient
    on the scale of their spread."""
    rng = np.random.default_rng(seed)
    z = rng.standard_normal((rows, features + 1))
    a = (z[:, 1:] + 0.5 * z[:, :-1]) / np.sqrt(1.25)
    a[:, 3] += ratio
    a[:, 10] -= ratio
    a = (a / np.sqrt((a * a).mean(axis=0))).astype(np.float32)
    w = np.where(np.arange(features) % 3 == 0, rng.uniform(0.25, 2.0, features) * rng.choice([-1, 1], features), 0.0)
    w[3], w[10] = 40.0, -25.0
    y = (a.astype(np.float64) @ w + 0.5 + 0.5 * rng.standard_normal(rows)).astype(np.float32)
    return a, y[:, None]


@pytest.mark.parametrize("seed", [7, 9])
@pytest.mark.parametrize("form, devices, bound", [("gram", 1, 5e-6), ("gram", 4, 5e-6), ("residual", 1, 5e-6)],
                         ids=["gram_kernel", "gram_mesh", "residual"])
def test_columns_far_off_zero_cost_no_precision(form, devices, bound, seed):
    """Two columns with ``mu / sigma`` of 50 (a Kelvin temperature).  float32
    normal equations of the table as it lies hold such a column as ``n (mu^2 +
    sigma^2)`` where the descent needs its ``n sigma^2``: before the
    equations stood about a shift the covariance form read 5.2e-5 to 1.3e-4
    of the largest coefficient from the float64 descent here (1.7e-4 to
    3.7e-4 at 20,000 rows and 1,000 sweeps), through the kernel and as a
    float32 XLA product alike.  About the shift (`lasso._shift`, the first
    rows' means; the sweeps in that frame, the steps dragging the intercept's
    place along) it reads 1.8e-7 to 1.0e-6 (4.5e-7 to 6.3e-6 at the larger
    size), on one device through the kernel and over the mesh; the residual
    form, which never forms ``G``, 3.1e-7 and 7.9e-7 (2.1e-5 to 2.9e-5).
    After 500 sweeps the coefficients are still moving: such a column
    converges slowly in any arithmetic."""
    rows, features, sweeps = 8192, 128, 500
    a, y = _off_zero_problem(rows, features, 50.0, seed)
    X = np.concatenate([np.ones((rows, 1)), a.astype(np.float64)], axis=1)
    lams = np.full(features + 1, 1e-3 * rows)
    lams[0] = 0.0
    want, _ = _float64_descent(X.T @ X, X.T @ y[:, 0].astype(np.float64), lams, sweeps)
    assert abs(want[4]) > 1 and abs(want[11]) > 0.1  # the two columns are in the model
    ht.use_comm(Communication(jax.devices()[:devices]))
    try:
        x, yy = ht.array(a, split=0), ht.array(y, split=0)
        plan = dict(n=rows, gram=form == "gram", syrk_ok=devices == 1, comm=x.comm if devices > 1 else None,
                    max_iter=sweeps, phase="fit")
        got, it, _ = lasso._program(x.larray_padded, yy.larray_padded, (), 1e-3 * rows, -1.0, None, **plan)
    finally:
        ht.use_comm(ht.WORLD)
    assert int(it) == sweeps
    off = np.max(np.abs(np.asarray(got, np.float64) - want)) / np.max(np.abs(want))
    assert off < bound, off

@pytest.mark.parametrize("rows, features, devices", [(301, 5, None), (37, 50, None), (4099, 128, 1)],
                         ids=["gram", "residual", "gram_through_the_kernel"])
def test_the_checkpointed_path_is_the_uninterrupted_fit_bit_for_bit(tmp_path, rows, features, devices):
    """A checkpointed fit prepares its equations in a program of their own
    (``phase="prepare"``) through the same function as the plain fit's one
    program; on one device and a table of a tile and more, that is the
    kernel's one read for the Gram and the moments both (PR 40)."""
    a, y = _problem(rows, features)
    if devices:
        ht.use_comm(Communication(jax.devices()[:devices]))
    try:
        x, y = ht.array(a, split=0), ht.array(y, split=0)
        kw = dict(lam=0.05 * rows, max_iter=11, tol=-1.0)
        plain = ht.regression.Lasso(**kw).fit(x, y)
        chunked = ht.regression.Lasso(**kw, checkpoint_every=4, checkpoint_dir=str(tmp_path / "ck")).fit(x, y)
    finally:
        ht.use_comm(ht.WORLD)
    assert lasso._one_read(rows, features, jnp.float32, True) == bool(devices)
    assert np.array_equal(plain.theta.numpy(), chunked.theta.numpy())
    assert plain.n_iter == chunked.n_iter == 11


def test_over_the_mesh_the_parts_are_summed_and_nothing_is_gathered(four_devices):
    a, y = _problem(4096, 128)
    x, y = ht.array(a, split=0), ht.array(y, split=0)
    plan = dict(n=4096, gram=True, syrk_ok=False, comm=x.comm, max_iter=3, phase="fit")
    text = lasso._program.lower(x.larray_padded, y.larray_padded[:, 0], (), jnp.float32(1), jnp.float32(-1),
                                jnp.zeros((129,), jnp.float32), **plan).compile().as_text()
    assert "all-reduce" in text and "all-gather" not in text and "all-to-all" not in text


# ------------------------------------------------------- the benchmark's yardstick
ROWS = 40000


@pytest.fixture(scope="module")
def driver():
    return load_py("drivers", "lasso_fit")


@pytest.fixture(scope="module")
def cfg():
    return load_json("configs", "lasso-1e7x128.json")


@pytest.fixture()
def state(driver, cfg, one_device):
    return driver.build(cfg, 3000000019, ROWS)


def test_the_drivers_table_is_the_configurations(driver, cfg, state):
    a, y = state["x"].numpy(), state["y"].numpy()
    assert a.shape == (ROWS, 128) and a.dtype == np.float32 and state["x"].split == 0 and y.shape == (ROWS, 1)
    p = state["p"]
    np.testing.assert_allclose((a.astype(np.float64) ** 2).mean(axis=0), 1.0, atol=0.03)  # unit second moment
    np.testing.assert_allclose(a.mean(axis=0), p["loc"], atol=0.02)
    assert np.count_nonzero(p["loc"]) == 32 and set(np.abs(p["loc"][p["loc"] != 0])) == {np.float32(0.25)}
    corr = np.corrcoef(a.T)
    np.testing.assert_allclose(np.diag(corr, 1), 0.4, atol=0.03)  # neighbours lean on each other: 0.5 / 1.25
    assert np.abs(np.diag(corr, 2)).max() < 0.03
    assert p["active"].size == 16 and np.diff(p["active"]).min() >= 3
    assert np.abs(p["size"]).min() == 0.25 and np.abs(p["size"]).max() == 2.0 and {-1.0, 1.0} == set(np.sign(p["size"]))
    model = a[:, p["active"]].astype(np.float64) @ p["size"] + 0.5
    assert abs(np.std(y[:, 0] - model) - 0.5) < 0.01
    again = driver.build(cfg, 3000000019, ROWS)
    assert np.array_equal(again["x"].numpy(), a) and np.array_equal(again["y"].numpy(), y)
    assert not np.array_equal(driver.build(cfg, 3000000020, ROWS)["x"].numpy(), a)
    assert state["lam"] == 0.05 * ROWS and state["max_iter"] == 100


def test_the_drivers_reference_is_the_definition(driver, state):
    ref = driver.reference(state)
    want, _ = np_lasso(state["x"].numpy(), state["y"].numpy(), state["lam"], state["max_iter"])
    np.testing.assert_allclose(ref["theta"], want, atol=3e-7)
    assert set(np.flatnonzero(ref["theta"][1:])) == set(state["p"]["active"])  # the support is the model's


def test_the_program_is_correct_by_the_drivers_limits(driver, cfg, state):
    out = driver.solve(state)
    numbers = driver.compare(state, out, driver.reference(state))
    assert judge(numbers, cfg["limits"])[0], numbers
    assert numbers["support_gap"] == 0 and numbers["n_iter_gap"] == 0


def test_the_control_is_refused(driver, cfg, state):
    numbers = driver.compare(state, driver.control(state), driver.reference(state))
    assert not judge(numbers, cfg["limits"])[0] and numbers["coef_dist"] > cfg["limits"]["coef_dist"], numbers


@pytest.mark.parametrize("fault,by", [("half", "coef_dist"), ("altered", "coef_dist"), ("unpenalised", "support_gap"),
                                      ("no_intercept", "coef_dist")])
def test_a_fault_is_refused_by_its_number(driver, cfg, state, fault, by):
    ref = driver.reference(state)
    with planted(driver.faults()[fault]):
        numbers = driver.compare(state, driver.solve(state), ref)
    assert numbers[by] > cfg["limits"][by], numbers
    if fault == "unpenalised":
        assert numbers["support_gap"] == 128 - 16


def test_a_gram_wrong_in_program_and_reference_alike_shows_in_kkt_gap(driver, cfg, state):
    """The pass that goes through no Gram: a reference whose normal equations
    are off (here its Gram scaled by 1 + 1e-4, and a program that agrees with
    it) is caught by ``kkt_gap`` alone."""
    ref = driver.reference(state)
    bent = {**ref, "G": ref["G"] * (1.0 + 1e-4)}
    bent["theta"] = driver._descend(bent["G"], bent["b"], state["lam"], state["max_iter"])
    numbers = driver.compare(state, {"theta": jnp.asarray(bent["theta"], jnp.float32), "n_iter": 100}, bent)
    assert numbers["coef_dist"] <= cfg["limits"]["coef_dist"] and numbers["kkt_gap"] > cfg["limits"]["kkt_gap"], numbers


def test_the_work_model(driver, cfg):
    n, f, it = 10_000_000, 128, 100
    assert (cfg["rows"], cfg["features"], cfg["max_iter"], cfg["tol"]) == (n, f, it, -1.0)
    assert driver.work(cfg) == {"bytes": n * f * 4 + n * 4, "operations": n * f * f + 4 * n * f + 2 * it * (f + 1) ** 2,
                                "gram_pass_bytes": n * f * 4}
    assert driver.work(cfg)["bytes"] == 5_160_000_000 and driver.work(cfg)["gram_pass_bytes"] == 5_120_000_000


def test_the_witness_refuses_a_descent_over_the_rows(driver, cfg, one_device, monkeypatch):
    """What the driver does to the program before PR 39: a fit whose loop takes
    a product over the table's rows for every coordinate exits with the reason
    before anything of the cell's size is made."""
    @jax.jit
    def rowwise(x, y, lam, tol, theta0):
        X = jnp.concatenate([jnp.ones((x.shape[0], 1), x.dtype), x], axis=1)

        def turn(j, t):
            rho = X[:, j] @ (y - X @ t + X[:, j] * t[j])
            return t.at[j].set(rho / jnp.sum(X[:, j] ** 2))

        return jax.lax.fori_loop(0, X.shape[1], turn, theta0), jnp.int32(1), jnp.float32(0)

    monkeypatch.setattr(lasso, "_rowwise", rowwise, raising=False)
    monkeypatch.setattr(lasso, "_lasso_fit", lambda x, y, lam, tol, theta0, **plan: lasso._rowwise(
        x, y.reshape(-1), lam, tol, jnp.zeros((x.shape[1] + 1,), x.dtype)))
    with pytest.raises(SystemExit, match="row extent"):
        driver._refuse_rowwise_descent(ht, cfg)
    monkeypatch.undo()
    driver._refuse_rowwise_descent(ht, cfg)  # the program as it stands passes
