"""Driver of the randomized PCA cells.

The timed entry is heat's own estimator as a user calls it:
``ht.decomposition.PCA(n_components=256, svd_solver="randomized",
iterated_power=4, n_oversamples=10, random_state=seed).fit(x)`` on a resident
float32 table, split 0, ended when ``components_``, ``singular_values_``,
``explained_variance_``, ``explained_variance_ratio_`` and ``mean_`` are
ready.

Everything below ``solve`` is the benchmark's own yardstick and imports
nothing of the program: the data generator (``X = (Z * s) R^T + mu``, ``Z``
standard normal from a counter-based hash of (row, column, seed), ``s`` a
power law over every column with no gap, ``R`` a seeded orthogonal matrix,
``mu`` the column means), the plain reference (the randomized PCA's own steps
from the estimator's sketch, its long sums and small factorizations in
float64 on the host), the comparison, the lower-precision control (the
reference's steps with every product of the table in one bfloat16 pass), the
faults that ``correct`` has to refuse and the work model.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import jax.scipy.linalg
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# A float32 dot on the MXU loses part of a long sum (drivers/hsvd_rank.py), so
# the reference sums blocks of this many rows on the device and the blocks in
# float64 on the host.
REF_BLOCK_ROWS = 16384
# Rows the generator makes a step: one step's normals and product are 128 MiB
# each beside the table.
MAKE_BLOCK_ROWS = 16384
# `altered` scales the largest singular value by this much: ten times the
# limit of `sv_rel` (configs/pca-randomized-emb2048.json).
ALTERED_BY = 5e-4


# ---------------------------------------------------------------- generator
def _params(seed: int, cfg: dict) -> dict:
    """From the seed: the hash's two keys, the rotation ``R`` (the Q of a
    Gaussian matrix's QR in float64, so that the axes are no coordinate
    axes), the column means and the spectrum ``s_j = (j + 1)^-decay``."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32])
    k0, k1 = (int(v) for v in rng.integers(0, 2 ** 32, 2, dtype=np.uint64))
    f = cfg["features"]
    rot, tri = np.linalg.qr(rng.standard_normal((f, f)))
    rot = rot * np.sign(np.diag(tri))[None, :]
    scales = (1.0 + np.arange(f)) ** -cfg["spectrum_decay"]
    mu = rng.uniform(*cfg["means"], f)
    return {"k0": np.uint32(k0), "k1": np.uint32(k1), "rot_t": rot.T.astype(np.float32),
            "scales": scales.astype(np.float32), "mu": mu.astype(np.float32)}


def _mix(h):
    """murmur3's finalizer: every input bit reaches every output bit."""
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _normal(k0, k1, r, c):
    """A standard normal at row ``r`` and column ``c`` (uint32).  The row is
    mixed before the column joins it: ``rows x 4096`` passes 2^32 here, and a
    linear key would make rows 2^20 apart alike."""
    h = _mix(_mix(_mix(r ^ k0) ^ c) ^ k1)
    # 23 bits and a half: exact in float32, so u never rounds up to 1
    u = ((h >> 9).astype(jnp.float32) + 0.5) * jnp.float32(2.0 ** -23)
    return jnp.float32(np.sqrt(2.0)) * jax.lax.erf_inv(2.0 * u - 1.0)


@partial(jax.jit, static_argnames=("rows", "bs"))
def _table(p: dict, rows: int, bs: int):
    """The table, a block of ``bs`` rows a step written into one buffer (the
    last block ends at the last row and makes again what the one before it
    made).  The product takes its operands rounded to bfloat16, so that a
    seed makes the same table on every backend; the reference reads the
    table as it is stored."""
    f = p["mu"].shape[0]
    cols = jax.lax.broadcasted_iota(jnp.uint32, (bs, f), 1)
    rot_t = p["rot_t"].astype(jnp.bfloat16)

    def body(i, buf):
        start = jnp.minimum(i * bs, rows - bs)
        r = (start + jax.lax.broadcasted_iota(jnp.int32, (bs, f), 0)).astype(jnp.uint32)
        z = (_normal(p["k0"], p["k1"], r, cols) * p["scales"][None, :]).astype(jnp.bfloat16)
        blk = jnp.matmul(z, rot_t, preferred_element_type=jnp.float32) + p["mu"][None, :]
        return jax.lax.dynamic_update_slice(buf, blk, (start, 0))

    return jax.lax.fori_loop(0, -(-rows // bs), body, jnp.zeros((rows, f), jnp.float32))


# ------------------------------------------------------------------- driver
def build(cfg: dict, seed: int, rows=None) -> dict:
    import heat_tpu as ht

    rows = rows or cfg["rows"]
    p = _params(seed, cfg)
    table = _table(p, rows, min(MAKE_BLOCK_ROWS, rows))
    x = ht.core.dndarray.DNDarray.from_dense(table, cfg["split"])
    del table
    return {"x": x, "rows": rows, "seed": seed, "mu": p["mu"],
            "fit": {k: cfg[k] for k in ("n_components", "iterated_power", "n_oversamples")},
            "notes": {"rows": rows, "features": cfg["features"], "components": cfg["n_components"]}}


def solve(state: dict) -> dict:
    """One solve: the public call, ended when every output a user reads is ready."""
    import heat_tpu as ht

    model = ht.decomposition.PCA(svd_solver="randomized", random_state=state["seed"], **state["fit"]).fit(state["x"])
    out = {"components": model.components_.larray_padded, "singular_values": model.singular_values_.larray_padded,
           "explained_variance": model.explained_variance_.larray_padded,
           "explained_variance_ratio": model.explained_variance_ratio_.larray_padded, "mean": model.mean_.larray_padded}
    jax.block_until_ready(out)
    return out


def work(cfg: dict, rows=None) -> dict:
    """The least one fit demands of the chip, from the shapes alone.  Reads of
    the table: the moments' one and two a turn of ``iterated_power + 1``
    turns (``X Q``, ``X^T Q``); the sketch (``rows x ell``): a turn writes
    ``X Q`` and reads its basis for ``X^T Q``, and its orthonormalization
    (two Gram-and-multiply passes) reads it four times and writes it twice.
    Operations: ``2 rows features ell`` a product, ``4 rows ell^2`` a pass of
    the orthonormalization, the moments' ``3 rows features``.  The small
    ``features x ell`` orthonormalizations and the factorizations are in
    neither bound: they are latency (``pca_factor_ms``).  ``product_*``: what
    one product demands, for ``pca_products_roofline_pct``."""
    n, f, k, q = rows or cfg["rows"], cfg["features"], cfg["n_components"], cfg["iterated_power"]
    ell = min(k + cfg["n_oversamples"], n, f)
    turns = q + 1
    table, sketch = n * f * 4, n * ell * 4
    return {"bytes": (2 * turns + 1) * table + turns * 8 * sketch,
            "operations": turns * (2 * 2 * n * f * ell + 2 * 4 * n * ell * ell) + 3 * n * f,
            "product_bytes": table, "product_operations": 2 * n * f * ell,
            # results as the device trace names them: the products' (an ell x rows and an ell x features
            # array), the sketch's passes' (its basis times a small matrix, its Gram) and the moments'
            "product_shapes": [f"f32[{ell},{n}]", f"f32[{ell},{f}]"], "sketch_shapes": [f"f32[{n},{ell}]", f"f32[{ell},{ell}]"],
            "moments_shapes": [f"(f32[{f}], f32[{f}])"]}


# ---------------------------------------------------------------- reference
def _blocks(rows: int) -> int:
    return -(-rows // REF_BLOCK_ROWS) if rows >= REF_BLOCK_ROWS else 1


def _block(x, shift, rows: int, bs: int, i):
    """Block ``i`` of `REF_BLOCK_ROWS` of the first ``rows`` rows (the rest
    is padding) about ``shift``, where it starts, and which of its rows are
    its own: the last block ends at the last row and owns only the rows no
    block before it took."""
    start = jnp.minimum(i * bs, rows - bs)
    own = start + jnp.arange(bs) >= i * bs
    return jax.lax.dynamic_slice_in_dim(x, start, bs, 0) - shift[None, :], start, own


@partial(jax.jit, static_argnames=("rows",))
def _moment_blocks(x, shift, rows: int):
    """Per block about ``shift``: the column sums and sums of squares."""
    bs = min(REF_BLOCK_ROWS, rows)

    def one(i):
        xb, _, own = _block(x, shift, rows, bs, i)
        xb = xb * own[:, None].astype(x.dtype)
        return jnp.sum(xb, axis=0), jnp.sum(xb * xb, axis=0)

    return jax.lax.map(one, jnp.arange(_blocks(rows)))


def _product(a, b, low: bool):
    """``a @ b`` at `highest`, or with ``low`` in ONE bfloat16 pass
    (operands rounded to bfloat16, float32 sums)."""
    if low:
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    return jnp.matmul(a, b, precision=HIGHEST)


@partial(jax.jit, static_argnames=("rows", "low"))
def _xq(x, mean, q, rows: int, low: bool):
    """``Xc Q`` (``rows x ell``), a block of rows at a time, ``Xc = x - mean``
    taken inside each block's product."""
    bs = min(REF_BLOCK_ROWS, rows)

    def body(i, y):
        xb, start, _ = _block(x, mean, rows, bs, i)
        return jax.lax.dynamic_update_slice(y, _product(xb, q, low), (start, 0))

    return jax.lax.fori_loop(0, _blocks(rows), body, jnp.zeros((rows, q.shape[1]), jnp.float32))


@partial(jax.jit, static_argnames=("rows", "low"))
def _qtx_blocks(x, mean, q, rows: int, low: bool):
    """``Q^T Xc`` a block of rows at a time (``blocks x ell x features``),
    for the host to sum in float64."""
    bs = min(REF_BLOCK_ROWS, rows)

    def one(i):
        xb, start, own = _block(x, mean, rows, bs, i)
        qb = jnp.where(own[:, None], jax.lax.dynamic_slice_in_dim(q, start, bs, 0), 0)
        return _product(qb.T, xb, low)

    return jax.lax.map(one, jnp.arange(_blocks(rows)))


@jax.jit
def _cholesky_qr2(y):
    """An orthonormal basis of ``y``'s columns: CholeskyQR, twice, at
    `highest`."""
    for _ in range(2):
        chol = jnp.linalg.cholesky(jnp.matmul(y.T, y, precision=HIGHEST))
        inv = jax.scipy.linalg.solve_triangular(chol, jnp.eye(y.shape[1], dtype=y.dtype), lower=True)
        y = jnp.matmul(y, inv.T, precision=HIGHEST)
    return y


def sketch_of(seed: int, features: int, ell: int):
    """The sketch the estimator draws with ``random_state=seed``: heat's
    generator seeded with it (``ht.random.seed``) and its first key, a
    threefry key ``fold_in(PRNGKey(seed), 0)``, turned into ``features x
    ell`` standard normals."""
    return jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(seed), 0), (features, ell), jnp.float32)


def _moments(state: dict):
    """The columns' means and variances, float64 from the blocks."""
    n = state["rows"]
    sums, squares = (np.asarray(a, np.float64).sum(axis=0)
                     for a in _moment_blocks(state["x"].larray_padded, jnp.asarray(state["mu"]), n))
    return state["mu"].astype(np.float64) + sums / n, (squares - sums * sums / n) / (n - 1)


def _randomized_steps(state: dict, low: bool) -> dict:
    """The randomized PCA the estimator is asked for, its steps taken here
    on their own: the same sketch (``sketch_of``), ``ell`` columns,
    ``iterated_power + 1`` turns of ``Y = Xc Q_f`` (the sketch the first
    time) and ``B = Q_n^T Xc`` with ``Q_n`` a basis of ``Y``, the next
    ``Q_f`` a basis of ``B^T``; the products a block of 16,384 rows at a
    time, ``B``'s blocks summed in float64 on the host; ``Q_n`` by
    CholeskyQR2 at `highest`, ``B``'s basis and the last ``B``'s SVD in
    float64 on the host; centred about the float64 mean rounded to float32
    inside each product.  ``low``: every product of the table in one
    bfloat16 pass."""
    n, fit, x = state["rows"], state["fit"], state["x"].larray_padded
    k, f = fit["n_components"], x.shape[1]
    ell = min(k + fit["n_oversamples"], n, f)
    mean, var = _moments(state)
    mean32 = jnp.asarray(mean, jnp.float32)
    q_f = sketch_of(state["seed"], f, ell)
    for turn in range(fit["iterated_power"] + 1):
        q_n = _cholesky_qr2(_xq(x, mean32, q_f, n, low))
        b = np.asarray(_qtx_blocks(x, mean32, q_n, n, low), np.float64).sum(axis=0)
        del q_n
        if turn < fit["iterated_power"]:
            q_f = jnp.asarray(np.linalg.qr(b.T)[0], jnp.float32)
    _, s, vt = np.linalg.svd(b, full_matrices=False)
    ev = s[:k] ** 2 / (n - 1)
    return {"components": vt[:k], "singular_values": s[:k], "explained_variance": ev,
            "explained_variance_ratio": ev / np.sum(var), "mean": mean, "spread": np.sqrt(var)}


def reference(state: dict) -> dict:
    return _randomized_steps(state, low=False)


def compare(state: dict, out: dict, ref: dict) -> dict:
    """Numbers of the last timed fit against the reference.

    ``sv_rel``: the largest relative error of a singular value.
    ``subspace_sine``: the sine of the largest principal angle between the
    fitted components' span and the reference's.  ``comp_dist``: the root
    mean square over the components of the distance between a fitted
    component and the reference's, its sign aligned (both of unit norm):
    with no gap in the spectrum two neighbours a hair apart turn into each
    other by rounding, so the largest distance of one pair is a matter of
    the seed, and the mean over the 256 is not.  ``mean_rel``: the largest error of a
    column's mean, in that column's standard deviations.  ``evr_gap``: the
    largest relative error of an explained variance ratio (its total from
    the moments, the fit's own)."""
    v = np.asarray(out["components"], np.float64)
    s = np.asarray(out["singular_values"], np.float64)
    ratio = np.asarray(out["explained_variance_ratio"], np.float64)
    vref = ref["components"]
    sign = np.sign(np.sum(v * vref, axis=1))
    sign[sign == 0] = 1.0
    return {
        "sv_rel": float(np.max(np.abs(s - ref["singular_values"]) / ref["singular_values"])),
        "subspace_sine": float(np.linalg.norm(v - (v @ vref.T) @ vref, 2)),
        "comp_dist": float(np.sqrt(np.mean(np.sum((v * sign[:, None] - vref) ** 2, axis=1)))),
        "mean_rel": float(np.max(np.abs(np.asarray(out["mean"], np.float64) - ref["mean"]) / ref["spread"])),
        "evr_gap": float(np.max(np.abs(ratio - ref["explained_variance_ratio"]) / ref["explained_variance_ratio"])),
    }


# ------------------------------------------------------------------ control
def control(state: dict) -> dict:
    """What `correct` has to refuse: the reference's own steps, its sketch,
    its float64 sums and factorizations, with every product of the table
    in ONE bfloat16 pass, the nearest precision below the `highest` the
    configuration states: ``x - mean`` and the basis rounded to bfloat16
    where the product reads them (the centring inside the product, as the
    program centres), float32 sums.  Nothing of the program is used."""
    return _randomized_steps(state, low=True)


# ------------------------------------------------------------------- faults
def faults() -> dict:
    """Faults planted under the timed path, {name: (module, attribute,
    maker)}: ``maker(original)`` takes the attribute's place.  Read at the
    cell's own size by ``chipbench.control`` and refused at rehearsal size by
    ``chipbench.selftest``: ``altered`` by ``sv_rel``, ``half`` by
    ``sv_rel`` (a singular value over half the rows is 1/sqrt(2) of the
    whole's) and by the components."""
    from heat_tpu.decomposition import pca

    def altered(original):  # an answer altered where it is produced: the largest singular value
        def f(*a, **kw):
            vt, s, ev, ratio, tevr = original(*a, **kw)
            return vt, s.at[0].multiply(1.0 + ALTERED_BY), ev, ratio, tevr
        return f

    def half(original):  # the rows from the middle on count for nothing: no copy of the table, the mask's rows
        def f(*a, n, **kw):
            return original(*a, n=n // 2, **kw)
        return f

    return {"altered": (pca, "_randomized_fit", altered), "half": (pca, "_randomized_fit", half)}
