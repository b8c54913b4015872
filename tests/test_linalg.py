"""Linalg tests across splits (reference: heat/core/linalg/tests)."""

import numpy as np
import pytest

import heat_tpu as ht

SPLITS = [None, 0, 1]


@pytest.fixture
def mats():
    rng = np.random.default_rng(11)
    a = rng.standard_normal((16, 12)).astype(np.float32)
    b = rng.standard_normal((12, 10)).astype(np.float32)
    return a, b


@pytest.mark.parametrize("sa", SPLITS)
@pytest.mark.parametrize("sb", SPLITS)
def test_matmul_all_split_combos(mats, sa, sb):
    a, b = mats
    A = ht.array(a, split=sa)
    B = ht.array(b, split=sb)
    C = ht.matmul(A, B)
    np.testing.assert_allclose(C.numpy(), a @ b, rtol=1e-5, atol=1e-5)


def test_matmul_batched(mats):
    rng = np.random.default_rng(12)
    a = rng.standard_normal((4, 8, 6)).astype(np.float32)
    b = rng.standard_normal((4, 6, 5)).astype(np.float32)
    for split in (None, 0, 1):
        C = ht.matmul(ht.array(a, split=split), ht.array(b, split=split if split == 0 else None))
        np.testing.assert_allclose(C.numpy(), a @ b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("split", SPLITS)
def test_qr(split):
    rng = np.random.default_rng(13)
    # 16 rows over 8 devices = 2/shard >= would fail n=12; TSQR needs m/p>=n,
    # so use a tall matrix for split=0
    a = rng.standard_normal((64, 8)).astype(np.float32) if split == 0 else rng.standard_normal((16, 12)).astype(np.float32)
    A = ht.array(a, split=split)
    q, r = ht.qr(A)
    np.testing.assert_allclose(q.numpy() @ r.numpy(), a, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(q.numpy().T @ q.numpy(), np.eye(q.shape[1]), atol=1e-4)
    # R upper triangular
    np.testing.assert_allclose(np.tril(r.numpy(), -1), 0.0, atol=1e-5)
    r_only = ht.qr(A, mode="r")
    assert r_only.Q is None
    np.testing.assert_allclose(np.abs(r_only.R.numpy()), np.abs(r.numpy()), rtol=1e-4, atol=1e-4)


def test_tsqr_uses_shard_map():
    # divisible tall-skinny split-0 -> TS-QR collective path
    rng = np.random.default_rng(14)
    a = rng.standard_normal((64, 4)).astype(np.float32)
    A = ht.array(a, split=0)
    q, r = ht.qr(A)
    assert q.split == 0 and r.split is None
    np.testing.assert_allclose(q.numpy() @ r.numpy(), a, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("p_dev", [3, 8])
def test_qr_ragged_sweep(p_dev):
    """Uneven extents on 3- and 8-device meshes never fall back to the
    gathering global path (reference qr.py:64 TS-QR + :220 block-GS)."""
    import importlib

    import jax
    from heat_tpu.parallel import Communication

    qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")

    if p_dev > len(jax.devices()):
        pytest.skip(f"lane has {len(jax.devices())} devices")
    comm = Communication(jax.devices()[:p_dev])
    rng = np.random.default_rng(21)
    tsqr_before = qr_mod._tsqr_fn.cache_info().misses
    bgs_before = qr_mod._bgs_fn.cache_info().misses
    for (m, n) in [(37, 5), (13, 4), (23, 23), (50, 13)]:
        for split in (0, 1):
            x = rng.standard_normal((m, n))
            A = ht.array(x, split=split, comm=comm)
            q, r = ht.qr(A)
            assert q.split == split and r.split == (None if split == 0 else 1)
            np.testing.assert_allclose(q.numpy() @ r.numpy(), x, atol=1e-10)
            np.testing.assert_allclose(q.numpy().T @ q.numpy(), np.eye(n), atol=1e-10)
            np.testing.assert_allclose(np.tril(r.numpy(), -1), 0.0, atol=1e-10)
    # both distributed kernels were exercised (no silent global fallback)
    assert qr_mod._tsqr_fn.cache_info().misses > tsqr_before
    assert qr_mod._bgs_fn.cache_info().misses > bgs_before


def test_qr_split1_wide_falls_back():
    # wide (m < n) split=1 goes through the dense path but stays correct
    rng = np.random.default_rng(22)
    x = rng.standard_normal((6, 20))
    A = ht.array(x, split=1)
    q, r = ht.qr(A)
    np.testing.assert_allclose(q.numpy() @ r.numpy(), x, atol=1e-10)


@pytest.mark.parametrize("split", SPLITS)
def test_svd(split):
    rng = np.random.default_rng(15)
    a = rng.standard_normal((40, 8)).astype(np.float32)
    A = ht.array(a, split=split)
    u, s, v = ht.svd(A)
    np.testing.assert_allclose(u.numpy() @ np.diag(s.numpy()) @ v.numpy().T, a, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(s.numpy(), np.linalg.svd(a, compute_uv=False), rtol=1e-4)


def test_hsvd_lowrank():
    rng = np.random.default_rng(16)
    u = np.linalg.qr(rng.standard_normal((64, 5)))[0]
    v = np.linalg.qr(rng.standard_normal((24, 5)))[0]
    s = np.array([10.0, 5.0, 2.0, 1.0, 0.5])
    a = (u * s) @ v.T
    a = a.astype(np.float32)
    for split in (None, 0, 1):
        A = ht.array(a, split=split)
        U, err = ht.linalg.hsvd_rank(A, 5)
        assert err < 1e-3
        proj = U.numpy() @ (U.numpy().T @ a)
        np.testing.assert_allclose(proj, a, rtol=1e-3, atol=1e-3)
        U2, S2, V2, err2 = ht.linalg.hsvd_rtol(A, 1e-3, compute_sv=True)
        np.testing.assert_allclose(S2.numpy(), s[: S2.shape[0]], rtol=1e-3)


def test_rsvd():
    rng = np.random.default_rng(17)
    a = (rng.standard_normal((50, 6)) @ rng.standard_normal((6, 30))).astype(np.float32)
    U, S, V = ht.linalg.rsvd(ht.array(a, split=0), rank=6, power_iter=1)
    np.testing.assert_allclose(U.numpy() @ np.diag(S.numpy()) @ V.numpy().T, a, rtol=1e-3, atol=1e-3)


def test_det_inv_trace():
    rng = np.random.default_rng(18)
    a = (rng.standard_normal((6, 6)) + 6 * np.eye(6)).astype(np.float32)
    for split in SPLITS:
        A = ht.array(a, split=split)
        np.testing.assert_allclose(ht.linalg.det(A).numpy(), np.linalg.det(a), rtol=1e-3)
        np.testing.assert_allclose(ht.linalg.inv(A).numpy(), np.linalg.inv(a), rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(ht.linalg.trace(A), np.trace(a), rtol=1e-5)


def test_norms_outer_dot():
    x = np.array([3.0, 4.0], dtype=np.float32)
    y = np.array([1.0, 2.0], dtype=np.float32)
    X = ht.array(x, split=0)
    Y = ht.array(y, split=0)
    assert float(ht.linalg.norm(X).numpy()) == pytest.approx(5.0, rel=1e-6)
    np.testing.assert_allclose(ht.linalg.outer(X, Y).numpy(), np.outer(x, y))
    np.testing.assert_allclose(ht.dot(X, Y).numpy(), np.dot(x, y))
    np.testing.assert_allclose(ht.vdot(X, Y).numpy(), np.vdot(x, y))
    np.testing.assert_allclose(
        ht.linalg.projection(X, Y).numpy(), (np.dot(x, y) / np.dot(y, y)) * y, rtol=1e-5
    )
    c1 = np.array([1.0, 0.0, 0.0], dtype=np.float32)
    c2 = np.array([0.0, 1.0, 0.0], dtype=np.float32)
    np.testing.assert_allclose(ht.cross(ht.array(c1), ht.array(c2)).numpy(), np.cross(c1, c2))


@pytest.mark.parametrize("split", SPLITS)
def test_tril_triu_transpose(split):
    rng = np.random.default_rng(19)
    a = rng.standard_normal((9, 7)).astype(np.float32)
    A = ht.array(a, split=split)
    np.testing.assert_allclose(ht.tril(A).numpy(), np.tril(a))
    np.testing.assert_allclose(ht.triu(A, 1).numpy(), np.triu(a, 1))
    np.testing.assert_allclose(ht.linalg.transpose(A).numpy(), a.T)


def test_cg_solve_triangular():
    rng = np.random.default_rng(20)
    n = 10
    a = rng.standard_normal((n, n)).astype(np.float32)
    spd = (a @ a.T + n * np.eye(n)).astype(np.float32)
    b = rng.standard_normal(n).astype(np.float32)
    x = ht.linalg.cg(ht.array(spd, split=0), ht.array(b, split=0), ht.zeros(n, split=0))
    np.testing.assert_allclose(spd @ x.numpy(), b, rtol=1e-3, atol=1e-3)

    r = np.triu(rng.standard_normal((n, n)) + 3 * np.eye(n)).astype(np.float32)
    sol = ht.linalg.solve_triangular(ht.array(r), ht.array(b[:, None]))
    np.testing.assert_allclose(r @ sol.numpy().ravel(), b, rtol=1e-3, atol=1e-3)


def test_lanczos_eigs():
    rng = np.random.default_rng(21)
    a = rng.standard_normal((24, 24)).astype(np.float32)
    sym = ((a + a.T) / 2).astype(np.float32)
    A = ht.array(sym, split=0)
    V, T = ht.linalg.lanczos(A, 24)
    evals = np.sort(np.linalg.eigvalsh(T.numpy()))
    expected = np.sort(np.linalg.eigvalsh(sym))
    np.testing.assert_allclose(evals[-3:], expected[-3:], rtol=1e-2, atol=1e-2)


def test_hsvd_rank_deficient(ht):
    # Gram-based fast path must drop noise-floor directions, not amplify
    # them (they live inside the dominant subspace and double-count energy)
    rng = np.random.default_rng(0)
    A = (rng.standard_normal((2000, 5)) @ rng.standard_normal((5, 64))).astype(np.float32)
    x = ht.array(A, split=0)
    u, s, v, err = ht.linalg.hsvd_rank(x, 10, compute_sv=True, safetyshift=5)
    U, S, V = u.numpy(), np.asarray(s._dense()), v.numpy()
    assert np.isfinite(U).all() and np.isfinite(V).all()
    rec = U @ np.diag(S) @ V.T
    rel = np.linalg.norm(A - rec) / np.linalg.norm(A)
    assert rel < 1e-4, rel


def test_rsvd_rank_deficient(ht):
    rng = np.random.default_rng(1)
    A = (rng.standard_normal((500, 4)) @ rng.standard_normal((4, 40))).astype(np.float32)
    x = ht.array(A, split=0)
    u, s, v = ht.linalg.rsvd(x, 6, n_oversamples=6)
    rec = u.numpy() @ np.diag(np.asarray(s._dense())) @ v.numpy().T
    rel = np.linalg.norm(A - rec) / np.linalg.norm(A)
    assert rel < 1e-4, rel


def test_hsvd_float64_high_condition(ht):
    # the Gram noise-floor cutoff must scale with dtype eps: an f64 matrix
    # with sigma spanning 4 decades keeps every direction f64 resolves
    rng = np.random.default_rng(3)
    q1, _ = np.linalg.qr(rng.standard_normal((400, 12)))
    q2, _ = np.linalg.qr(rng.standard_normal((32, 12)))
    sv = np.logspace(0, -4, 12)
    A = (q1 * sv) @ q2.T
    x = ht.array(A, split=0)  # float64 under the suite's x64 mode
    u, s, v, err = ht.linalg.hsvd_rank(x, 12, compute_sv=True, safetyshift=0)
    np.testing.assert_allclose(np.asarray(s._dense()), sv, rtol=1e-8)
    rec = u.numpy() @ np.diag(np.asarray(s._dense())) @ v.numpy().T
    assert np.linalg.norm(A - rec) / np.linalg.norm(A) < 1e-8


# --- the fixed-rank path projects at the final rank (PR 26) -----------------
# _hsvd_rank_jit hands its static k down to _hsvd_body, which takes the
# full-height products at k columns; _hsvd_core (the rtol path) keeps the
# working width.  The kept columns must be _hsvd_core's.


def _hsvd_matrix(m, n, rank=None, seed=0):
    rng = np.random.default_rng(seed)
    if rank is not None:  # rank-deficient: the keep mask zeroes columns past `rank`
        return (rng.standard_normal((m, rank)) @ rng.standard_normal((rank, n))).astype(np.float32)
    return (rng.standard_normal((m, n)) * np.geomspace(1.0, 1e-3, n)).astype(np.float32)


def _assert_columns_close(got, want, tol=1e-6):
    """Each column of ``got`` within ``tol`` of the column's norm from ``want``'s."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, (got.shape, want.shape)
    dist = np.linalg.norm(got - want, axis=0)
    assert (dist <= tol * np.linalg.norm(want, axis=0)).all(), dist


def _rank_against_core(a, trunc, p, k, syrk_ok=False):
    import jax.numpy as jnp

    from heat_tpu.core.linalg.svdtools import _hsvd_core, _hsvd_rank_jit

    dense = jnp.asarray(a)
    u, s, v, err = _hsvd_rank_jit(dense, trunc, p, 2, k, True, "float32", syrk_ok=syrk_ok)
    u_w, s_w, v_w, _disc, total_sq = _hsvd_core(dense, trunc, p, 2, syrk_ok=syrk_ok)
    want_err = jnp.sqrt(
        jnp.maximum(total_sq - jnp.sum(s_w[:k].astype(jnp.float32) ** 2), 0.0)
        / jnp.maximum(total_sq, 1e-30)
    )
    return (u, s, v, err), (u_w[:, :k], s_w[:k], v_w[:, :k], want_err)


@pytest.mark.parametrize(
    "m, n, maxrank, safetyshift, rank, syrk_ok",
    [
        (6155, 128, 10, 5, None, False),  # the shape tried in sizing
        (6155, 128, 10, 5, None, True),   # through gram_syrk's gate
        (500, 64, 10, 0, None, False),    # k == trunc: the bypass
        (300, 12, 10, 5, None, False),    # trunc > n
        (300, 8, 12, 5, None, False),     # maxrank > n: k is wider than U can be
        (2000, 64, 10, 5, 5, False),      # keep mask zeroes columns 5..9, inside the first k
        (2000, 64, 10, 0, 5, False),      # the same with k == trunc
    ],
)
def test_hsvd_rank_single_leaf_keeps_cores_columns(m, n, maxrank, safetyshift, rank, syrk_ok):
    a = _hsvd_matrix(m, n, rank, seed=m + n)
    trunc = min(maxrank + safetyshift, m)
    k = min(maxrank, trunc)
    (u, s, v, err), (u_w, s_w, v_w, err_w) = _rank_against_core(a, trunc, 1, k, syrk_ok)
    assert u.shape == (m, min(k, n)) and v.shape == (n, min(k, n)) and s.shape == (min(k, n),)
    _assert_columns_close(u, u_w)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_w))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_w))
    np.testing.assert_array_equal(np.asarray(err), np.asarray(err_w))
    if rank is not None:  # dropped directions are zero columns, value and vector together
        dropped = np.asarray(s) == 0
        assert dropped[rank:].any() and not dropped[:rank].any()
        assert (np.asarray(u)[:, dropped] == 0).all()


@pytest.mark.parametrize(
    "m, n, p, maxrank, safetyshift",
    [
        (512, 128, 4, 10, 5),   # two merge levels
        (512, 96, 3, 10, 5),    # an odd leaf count
        (512, 128, 8, 10, 0),   # k == trunc in the tree
        (40, 64, 1, 10, 5),     # one wide leaf: the generic path without a merge
        (200, 64, 4, 20, 5),    # trunc wider than a leaf (16 columns)
    ],
)
def test_hsvd_rank_merge_tree_keeps_cores_columns(m, n, p, maxrank, safetyshift):
    a = _hsvd_matrix(m, n, seed=m + n + p)
    trunc = min(maxrank + safetyshift, m)
    k = min(maxrank, trunc)
    (u, s, v, err), (u_w, s_w, v_w, err_w) = _rank_against_core(a, trunc, p, k)
    assert u.shape == (m, k) and v.shape == (n, k)
    _assert_columns_close(u, u_w)
    _assert_columns_close(v, v_w)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_w))
    np.testing.assert_allclose(np.asarray(err), np.asarray(err_w), rtol=1e-6)


@pytest.mark.parametrize("split", [0, 1])
def test_hsvd_rank_public_api_keeps_cores_columns(split):
    """``split=1`` on the suite's mesh is the merge tree over the mesh's column
    blocks; ``split=0`` there is the single leaf with the XLA Gram."""
    import jax.numpy as jnp

    from heat_tpu.core.linalg.svdtools import _hsvd_core

    a = _hsvd_matrix(1024, 128, seed=split)
    A = ht.array(a, split=split)
    U, S, V, err = ht.linalg.hsvd_rank(A, 10, compute_sv=True)
    p = A.comm.size if split == 1 else 1
    u_w, s_w, v_w, _disc, _total = _hsvd_core(
        A._dense().astype(jnp.float32), 15, p, 2, syrk_ok=A.comm.size == 1
    )
    assert U.shape == (1024, 10) and V.shape == (128, 10)
    _assert_columns_close(U.numpy(), u_w[:, :10])
    _assert_columns_close(V.numpy(), v_w[:, :10])
    np.testing.assert_array_equal(S.numpy(), np.asarray(s_w[:10]))
    rec = U.numpy() @ np.diag(S.numpy()) @ V.numpy().T
    assert np.linalg.norm(a - rec) / np.linalg.norm(a) <= 1.01 * float(err) + 1e-5


def _tall_equations(jaxpr, rows):
    """(primitive, columns) of every equation, nested programs included, that
    yields a 2-d array of ``rows`` rows; the jit calls themselves left out."""
    import jax

    found = []
    for eqn in jaxpr.eqns:
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        for sub in subs:
            found += _tall_equations(sub, rows)
        if subs:
            continue
        for out in eqn.outvars:
            shape = getattr(out.aval, "shape", ())
            if len(shape) == 2 and shape[0] == rows:
                found.append((eqn.primitive.name, shape[1]))
    return found


@pytest.mark.parametrize(
    "p, leaves_and_merges",
    [
        (1, []),
        # two column blocks, their U s factors, the concatenation, its U s factor
        (2, [("slice", 64), ("slice", 64), ("dot_general", 15), ("dot_general", 15),
             ("concatenate", 30), ("dot_general", 15)]),
    ],
)
def test_hsvd_rank_path_writes_nothing_tall_wider_than_k(p, leaves_and_merges):
    """At (8192, 128), trunc 15, k 10: past the leaf and merge levels no
    equation of the rank path yields 8,192 rows at more than 10 columns; the
    rtol path's program still has its (m, trunc) result."""
    import jax
    import jax.numpy as jnp

    from heat_tpu.core.linalg import svdtools

    x = jax.ShapeDtypeStruct((8192, 128), jnp.float32)
    rank_path = _tall_equations(jax.make_jaxpr(
        lambda d: svdtools._hsvd_rank_jit(d, 15, p, 2, 10, True, "float32"))(x).jaxpr, 8192)
    rtol_path = _tall_equations(jax.make_jaxpr(
        lambda d: svdtools._hsvd_core(d, 15, p, 2))(x).jaxpr, 8192)
    assert [e for e in rank_path if e[1] > 10] == leaves_and_merges
    assert rank_path[len(leaves_and_merges):] == [("dot_general", 10), ("mul", 10)]
    assert rtol_path == leaves_and_merges + [("dot_general", 15), ("mul", 15)]


def test_hsvd_rank_with_k_equal_trunc_is_the_rtol_paths_program():
    """``safetyshift=0``: nothing to narrow, so the body's equations are the
    ones it has without an output width."""
    import jax
    import jax.numpy as jnp

    from heat_tpu.core.linalg import svdtools

    x = jax.ShapeDtypeStruct((8192, 128), jnp.float32)
    for p in (1, 2):
        narrowed = jax.make_jaxpr(
            lambda d: svdtools._hsvd_body(d, 10, p, 2, True, out_width=10))(x)
        plain = jax.make_jaxpr(lambda d: svdtools._hsvd_body(d, 10, p, 2, True))(x)
        assert str(narrowed) == str(plain)
