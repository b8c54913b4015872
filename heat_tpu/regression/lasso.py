"""Lasso regression, analog of heat/regression/lasso.py (lasso.py:10).

Cyclic coordinate descent with soft thresholding on the objective
``1/2 |y - X theta|^2 + lam |theta[1:]|_1``, in SUMS over the rows (upstream's
update takes means: its ``lam`` is this one over the number of rows).

A fit reads the table for what the descent needs and then descends on small
numbers.  On a tall table (`_gram_form`) that is the covariance form: with
``G = X^T X`` and ``b = X^T y``, coordinate ``j``'s update is
``rho = b_j - sum_{k != j} G_jk theta_k``, the soft threshold and the division
by ``G_jj``: the iterates of the definition (the residual recomputed for every
coordinate) from ONE read of the table (`_gram_and_moments`: the Pallas kernel
``gram_syrk`` that hSVD's Gram uses, on one device, takes the moments, the
columns' sums, their products with ``y`` and their sums of squares, from the
tiles it reads for the Gram; over a mesh, or on a table the kernel refuses, an
XLA product and a second read for the moments) and ``max_iter`` sweeps over
``(f + 1)^2`` numbers in ONE kernel (`kernels.cd_sweeps`).  The intercept is no column of the table: its row of
``G`` is the columns' sums and the number of rows.  Where ``G`` would not be
small beside the table, or the kernel does not take it, the residual form
runs: ONE residual kept up to date coordinate by coordinate, recomputed once a
sweep.  Either way a fit is one program, enqueued once, and reads nothing back.

Precision.  Everything is float32 (the kernel's products compensated
bfloat16, three terms, whose errors are random from row to row and average
out; its one systematic part, a diagonal 2.76e-6 low, is replaced by the
moments' sums of squares).  float32 normal equations of the table AS IT LIES
would lose what an uncentred table loses: a column with mean ``mu`` and spread
``sigma`` enters ``G`` as ``n (mu^2 + sigma^2)`` where the descent needs its
``n sigma^2``, ``2.5e-7 (mu / sigma)^2`` of the coefficient: 6e-4 at a
``mu / sigma`` of 50 (a Kelvin temperature), whatever computes the products.
So the equations stand about a SHIFT near the columns' means (`_shift`; any
shift gives the same iterates in exact arithmetic), taken from the values as
the table's read takes them, and the sweeps run in that frame (`_normal_equations`):
such a table then reads 2e-7 to 6e-6 of its largest coefficient from a float64
descent, as near as the residual form (`tests/test_lasso_reference.py`).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as _P

from ..core import dispatch, kernels, types
from ..core.base import BaseEstimator, RegressionMixin, lazy_scalar_property
from ..core.dndarray import DNDarray
from ..telemetry.spans import span as _span

#: rows a partial sum of the moments' pass covers.  One sum over all of 10^7
#: rows reads the squares 1.4e-6 off and ``X^T y`` 2.4e-6 of its scale off;
#: blocks of 2^14 / 2^16 / 2^18 rows read 9.9e-7 / 5.0e-7 / 2.3e-7 and
#: 7.4e-7 / 3.9e-7 / 2.4e-7, in 10.38 / 7.98 / 7.21 ms for the one sum's 7.01
#: (a block is a turn of a loop, nine operations; chip run, PERF.md, PR 39)
_SUM_BLOCK_ROWS = 1 << 18
#: rows (the first a device holds) whose means are the frame's shift
_SHIFT_ROWS = 4096
_TINY = 1e-30


def _linear_predict_op(xd, th):
    """Intercept + coefficients in one cached program (the predict hot
    path the serving layer batches)."""
    yest = jnp.matmul(xd, th[1:], precision=jax.lax.Precision.HIGHEST) + th[0]
    return yest.reshape(-1, 1)


def _soft_threshold_op(d, *, lam):
    return jnp.sign(d) * jnp.maximum(jnp.abs(d) - lam, 0.0)


def _gram_form(n: int, f: int, dtype) -> bool:
    """THE rule for which form a fit runs: the covariance form where the
    ``(f + 1)^2`` numbers of the normal equations are at most a quarter of
    the table's ``n f`` and the descent's kernel takes them (float32, up to
    1,024 coordinates: `kernels.cd_supported`); the residual form on a table
    wider than that, or of another type.  The first clause is one of
    conditioning, not of speed: right at it (528 x 128, 2,064 x 512) the
    covariance form is 21 and 24 times the faster (chip run, PERF.md,
    PR 39), but with fewer rows than columns ``G`` is singular and float32
    sweeps on it drift (after 500 sweeps 2.3e-4 from the definition at 20 x
    128 and 9e-5 at 100 x 300, where the residual form keeps 3.5e-6 and
    4.3e-6)."""
    return 4 * (f + 1) ** 2 <= n * f and kernels.cd_supported(f + 1, dtype)


def _own(a):
    """What a sum over the rows is where one device holds them all."""
    return a


def _shift(x, y, real, all_sum):
    """The frame's shift: the means of the first `_SHIFT_ROWS` rows a device
    holds, of the columns and of the targets.  ANY shift gives the same
    iterates in exact arithmetic; one near the means lets float32 hold the
    columns' spreads.  (A table whose first rows are nothing like the rest
    keeps what the unshifted form had.)"""
    k = min(x.shape[0], _SHIFT_ROWS)
    if real is None:
        return jnp.mean(x[:k], axis=0), jnp.mean(y[:k])
    count = jnp.maximum(all_sum(jnp.sum(real[:k])), 1)
    return all_sum(jnp.sum(x[:k] * real[:k, None], axis=0)) / count, all_sum(jnp.sum(y[:k] * real[:k])) / count


def _moments(x, y, c, cy, real):
    """What the normal equations need beside the Gram, in ONE read of the
    table, of the columns less ``c`` and the targets less ``cy``: the
    columns' sums, their products with the targets and their sums of squares
    (``(3, f)``), and the targets' sum.  ``real`` (None: every row) is 1 on
    the rows that count, 0 on padding.  Partial sums over blocks of
    `_SUM_BLOCK_ROWS` rows, read where they lie, then the sum of those.
    Where the Gram's kernel takes the table it takes these too, and this
    pass is not made (`_gram_and_moments`)."""
    m, bs = x.shape[0], _SUM_BLOCK_ROWS
    nb = m // bs

    def sums(xs, ys, rs):
        xs, ys = xs - c, ys - cy
        if rs is not None:
            xs, ys = xs * rs[:, None], ys * rs
        return (jnp.stack([jnp.sum(xs, axis=0), jnp.sum(xs * ys[:, None], axis=0), jnp.sum(xs * xs, axis=0)]),
                jnp.sum(ys))

    def part(a, start, rows):
        return None if a is None else jax.lax.dynamic_slice_in_dim(a, start, rows)

    with jax.named_scope("lasso.moments"):
        of_x, of_y = sums(*(part(a, nb * bs, m - nb * bs) for a in (x, y, real)))  # the rows past the last whole block
        if nb:
            bx, by = jax.lax.map(lambda i: sums(*(part(a, i * bs, bs) for a in (x, y, real))), jnp.arange(nb))
            of_x, of_y = of_x + jnp.sum(bx, axis=0), of_y + jnp.sum(by)
        return of_x, of_y


def _one_read(m: int, f: int, dtype, syrk_ok: bool) -> bool:
    """Lasso's own rule for the one-read kernel ``gram_syrk`` (which hSVD's
    Gram uses too): where one device holds the rows (``syrk_ok``, the
    caller's static layout gate) and the kernel takes the shape, it reads the
    table once for the Gram and the moments both.  No environment variable
    steers it: hSVD's ``HEAT_TPU_HSVD_*`` and the reason it gives for them (a
    truncation error that covers the Gram's) are hSVD's."""
    return syrk_ok and kernels.syrk_supported(m, f, dtype)


def _gram_and_moments(x, y, c, cy, syrk_ok: bool, real):
    """The Gram of the columns less ``c`` and the moments (`_moments`):
    ``(G, s1, bxy, q, sy)``.  Where `_one_read` holds, ONE read of the table:
    the kernel takes the moments from the tiles it reads for the Gram.
    Elsewhere (over a mesh, or a table the kernel refuses) two: an XLA
    product in float32 (``HIGHEST``) and the moments' own pass."""
    if real is None and _one_read(*x.shape, x.dtype, syrk_ok):
        with jax.named_scope("lasso.gram"):
            return kernels.gram_syrk(x, c, y, cy)
    with jax.named_scope("lasso.gram"):
        xc = x - c if real is None else (x - c) * real[:, None]
        gc = jnp.matmul(xc.T, xc, precision=jax.lax.Precision.HIGHEST)
    (s1, bxy, q), sy = _moments(x, y, c, cy, real)
    return gc, s1, bxy, q, sy


def _normal_equations(x, y, n: int, syrk_ok: bool, all_sum, real=None):
    """The normal equations of ``X = [1, x]`` in the SHIFTED frame, from the
    rows held here (``all_sum`` adds the devices' parts; ``n`` counts the
    rows of all of them), in one read of the table where the kernel takes it
    and two elsewhere (`_gram_and_moments`): ``(A, b, col_sq, drag, cy)`` as
    `kernels.cd_sweeps` takes them.  With ``xc = x - c``, ``yc =
    y - cy`` and the unknowns ``t = (u, theta)``, ``u = theta_0 + c . theta -
    cy``, the residual is ``yc - u - xc theta``; ``G' = [1, xc]^T [1, xc]``
    and ``b' = [1, xc]^T yc`` hold numbers of the size of the columns'
    SPREADS.  Coordinate ``j``'s column is ``x_j = xc_j + c_j``, so its
    product with the residual is ``b_j - A_j . t`` with ``A_j = G'_j + c_j
    G'_0`` and ``b_j = b'_j + c_j b'_0``, its sum of squares ``col_sq_j``,
    and its step drags ``u`` along by ``c_j`` (``drag``).  The Gram's
    diagonal is the moments' sum of squares: the kernel's three-term product
    reads a diagonal 2.76e-6 low (PERF.md section 4), which is what values
    rounded to bfloat16 read high."""
    c, cy = _shift(x, y, real, all_sum)
    gc, s1, bxy, q, syc = all_sum(_gram_and_moments(x, y, c, cy, syrk_ok, real))
    f, count = x.shape[1], jnp.full((1,), n, x.dtype)
    gc = jnp.where(jnp.eye(f, dtype=bool), q[None, :], gc)
    top = jnp.concatenate([count, s1])
    G = jnp.concatenate([top[None, :], jnp.concatenate([s1[:, None], gc], axis=1)], axis=0)
    drag = jnp.concatenate([jnp.zeros((1,), x.dtype), c])
    col_sq = jnp.concatenate([count, q + 2 * c * s1 + n * c * c])
    return G + drag[:, None] * top[None, :], jnp.concatenate([syc[None], bxy]) + drag * syc, col_sq, drag, cy


def _enter(theta, made, gram: bool):
    """``theta`` as the sweeps hold it: in the covariance form the intercept's
    place holds ``u = theta_0 + c . theta - cy``."""
    if not gram:
        return theta
    *_, drag, cy = made
    return theta.at[0].add(jnp.sum(drag * theta) - cy)


def _leave(t, made, gram: bool):
    """`_enter`'s way back: the intercept of what the sweeps hold."""
    if not gram:
        return t
    *_, drag, cy = made
    return t.at[0].add(cy - jnp.sum(drag * t))


def _threshold(rho, lam, col_sq):
    """A coordinate's new value from its ``rho``: the soft threshold over the
    column's sum of squares (``lam`` 0 for the intercept, which is not
    penalized)."""
    return _soft_threshold_op(rho, lam=lam) / jnp.maximum(col_sq, _TINY)


def _sweeps(one_sweep, theta0, tol, max_iter: int):
    """At most ``max_iter`` sweeps from ``theta0``, ended by a sweep that
    moves no coordinate by ``tol``: (theta, sweeps run, last move)."""
    def cond(carry):
        _, it, delta = carry
        return jnp.logical_and(it < max_iter, delta >= tol)

    def body(carry):
        th, it, _ = carry
        new = one_sweep(th)
        return new, it + 1, jnp.max(jnp.abs(new - th)).astype(jnp.float32)

    return jax.lax.while_loop(cond, body, (theta0, jnp.int32(0), jnp.asarray(jnp.inf, jnp.float32)))


def _descend_gram(A, b, col_sq, drag, cy, lam, tol, t0, max_iter: int):
    """The descent on the normal equations, ONE kernel: they lie in a core's
    vector memory whole (`kernels.cd_sweeps`; `_gram_form` lets no others
    come here)."""
    with jax.named_scope("lasso.cd"):
        lams = jnp.full(b.shape, lam, b.dtype).at[0].set(0)  # the intercept is not penalized
        return kernels.cd_sweeps(A, b, lams, col_sq, drag, tol, t0, max_iter)


def _descend_residual(x, y, col_sq, n: int, first, lam, tol, theta0, max_iter: int, all_sum):
    """The descent on the table itself (wide tables): the residual is made
    once a sweep and kept up to date coordinate by coordinate, a column of
    the table read a turn.  The intercept's column is the ones of the real
    rows (the block starts at global row ``first``; rows from ``n`` on are
    padding)."""
    with jax.named_scope("lasso.cd"):
        f = x.shape[1]
        ones = (first + jnp.arange(x.shape[0]) < n).astype(x.dtype)
        count = jnp.asarray(n, x.dtype)

        def one_sweep(th):
            r = y - jnp.matmul(x, th[1:], precision=jax.lax.Precision.HIGHEST) - th[0] * ones
            new0 = _threshold(all_sum(jnp.sum(r)) + count * th[0], 0, count)  # not penalized
            r = r - ones * (new0 - th[0])

            def turn(j, carry):
                t, r = carry
                col = jax.lax.dynamic_index_in_dim(x, j, 1, keepdims=False)
                new = _threshold(all_sum(jnp.sum(col * r)) + col_sq[j] * t[j + 1], lam, col_sq[j])
                return t.at[j + 1].set(new), r - col * (new - t[j + 1])

            return jax.lax.fori_loop(0, f, turn, (th.at[0].set(new0), r))[0]

        return _sweeps(one_sweep, theta0, tol, max_iter)


def _prepare(x, y, n: int, gram: bool, syrk_ok: bool, all_sum, real):
    """What the sweeps run on, made once a fit: the normal equations, or for
    the residual form the columns' sums of squares (padding is zeros there
    and adds nothing)."""
    if gram:
        return _normal_equations(x, y, n, syrk_ok, all_sum, real)
    nothing = jnp.zeros((), x.dtype)
    return (all_sum(_moments(x, y, nothing, nothing, None)[0][2]),)


def _descend(x, y, made, n: int, first, gram: bool, lam, tol, t0, max_iter: int, all_sum):
    if gram:
        return _descend_gram(*made, lam, tol, t0, max_iter)
    return _descend_residual(x, y, *made, n, first, lam, tol, t0, max_iter, all_sum)


@partial(jax.jit, static_argnames=("n", "gram", "syrk_ok", "comm", "max_iter", "phase"))
def _program(x, y, made, lam, tol, theta0, *, n: int, gram: bool, syrk_ok: bool, comm, max_iter: int, phase: str):
    """The fit's programs on the rows as they lie (``comm`` None: one device,
    or rows no mesh divides; else each device takes its rows' part of every
    sum and the parts are all-reduced, ``G`` is 66 KB at 128 columns).
    ``phase`` ``fit``: everything, ``(theta, sweeps, last move)`` from
    ``theta0`` (None: zeros).  The checkpointed path runs the same bodies in
    parts: ``prepare`` makes ``made`` once, ``enter`` turns ``theta0`` into
    what the sweeps hold (`_enter`), ``descend`` runs a chunk of the same
    sweeps on that, ``leave`` turns it back.  ``y`` comes as the caller
    holds it, ``(rows, 1)`` or ``(rows,)``; ``lam`` and ``tol`` as numbers or
    arrays."""
    def run(x, y, made, lam, tol, theta0, all_sum, first=0, real=None):
        # the small conversions happen here, inside the program: eagerly each is a launch of its own
        y = y.reshape(-1).astype(x.dtype)
        lam, tol = jnp.asarray(lam, x.dtype), jnp.asarray(tol, jnp.float32)
        theta0 = jnp.zeros((x.shape[1] + 1,), x.dtype) if theta0 is None else jnp.asarray(theta0, x.dtype)
        if phase in ("fit", "prepare"):
            # behind a barrier, so that the sweeps see in one program what they see in two
            made = jax.lax.optimization_barrier(_prepare(x, y, n, gram, syrk_ok, all_sum, real))
        if phase == "prepare":
            return made
        if phase in ("enter", "leave"):
            return (_enter if phase == "enter" else _leave)(theta0, made, gram)
        t0 = jax.lax.optimization_barrier(_enter(theta0, made, gram)) if phase == "fit" else theta0
        t, sweeps, moved = _descend(x, y, made, n, first, gram, lam, tol, t0, max_iter, all_sum)
        return (_leave(jax.lax.optimization_barrier(t), made, gram) if phase == "fit" else t), sweeps, moved

    if comm is None:
        return run(x, y, made, lam, tol, theta0, _own)
    rows = _P(comm.axis_name)

    def on_a_device(x, *rest):
        first = jax.lax.axis_index(comm.axis_name) * x.shape[0]
        return run(x, *rest, all_sum=comm.psum, first=first, real=(first + jnp.arange(x.shape[0]) < n).astype(x.dtype))

    return _shard_map(on_a_device, mesh=comm.mesh,
                      in_specs=(rows, rows, _P(), _P(), _P(), _P()), out_specs=_P(), check_vma=False)(
        x, y, made, lam, tol, theta0)


def _lasso_fit(x, y, lam, tol, theta0, **plan):
    """The whole fit, ONE launch whatever the mesh; looked up by name where
    ``fit`` calls it."""
    return _program(x, y, (), lam, tol, theta0, phase="fit", **plan)


__all__ = ["Lasso"]


class Lasso(BaseEstimator, RegressionMixin):
    """L1-regularized linear regression via coordinate descent (lasso.py:10).

    ``checkpoint_every=N`` + ``checkpoint_dir`` checkpoint ``theta``
    every N sweeps through the filesystem-native Checkpointer;
    ``resume_from=dir`` continues a killed fit from its last checkpoint
    with the identical sweep sequence (the resumed result matches the
    uninterrupted one exactly).  The chunked path raises
    :class:`~heat_tpu.resilience.DivergenceError` on NaN/Inf."""

    def __init__(
        self,
        lam: float = 0.1,
        max_iter: int = 100,
        tol: float = 1e-6,
        checkpoint_every: Optional[int] = None,
        checkpoint_dir: Optional[str] = None,
        resume_from: Optional[str] = None,
    ):
        from ..core.base import validate_resume_params

        validate_resume_params(checkpoint_every, checkpoint_dir, resume_from)
        self.__lam = lam
        self.max_iter = max_iter
        self.tol = tol
        self.checkpoint_every = checkpoint_every
        self.checkpoint_dir = checkpoint_dir
        self.resume_from = resume_from
        self.__theta = None
        self._n_iter = None

    @property
    def coef_(self) -> Optional[DNDarray]:
        return None if self.__theta is None else self.__theta[1:]

    @property
    def intercept_(self) -> Optional[DNDarray]:
        return None if self.__theta is None else self.__theta[0]

    @property
    def lam(self) -> float:
        return self.__lam

    @lam.setter
    def lam(self, arg: float):
        self.__lam = arg

    @property
    def theta(self):
        return self.__theta

    def soft_threshold(self, rho):
        """Soft-thresholding operator (lasso.py:80).

        The sign/max/abs chain runs as ONE cached executable through the
        dispatch layer — a regularization-path sweep calling this per
        lambda re-uses the compiled program instead of paying three
        eager launches each time."""
        lam = float(self.__lam)
        if isinstance(rho, DNDarray):
            out = dispatch.eager_apply(_soft_threshold_op, (rho._dense(),), {"lam": lam})
            return DNDarray.from_dense(out, rho.split, rho.device, rho.comm)
        return dispatch.eager_apply(_soft_threshold_op, (jnp.asarray(rho),), {"lam": lam})

    def rmse(self, gt: DNDarray, yest: DNDarray) -> float:
        """Root mean squared error (lasso.py:100)."""
        diff = gt._dense().ravel() - yest._dense().ravel()
        return float(jnp.sqrt(jnp.mean(diff * diff)))

    # fit stores the device scalar so it never blocks on a device->host sync
    n_iter = lazy_scalar_property("_n_iter", int)

    def fit(self, x: DNDarray, y: DNDarray) -> "Lasso":
        """Cyclic coordinate descent from ``theta = 0`` (lasso.py:120).

        The intercept comes first and is not penalized; the objective is
        ``1/2 |y - X theta|^2 + lam |theta[1:]|_1``: ``lam`` multiplies the
        coefficients' magnitudes against a SUM of squares over the rows, so
        the same shrinkage on twice the rows takes twice the ``lam``
        (upstream's update takes means).

        Where the ``(features + 1)^2`` numbers of the normal equations are at
        most a quarter of the table's and the descent's kernel takes them
        (float32, up to 1,024 coordinates: `_gram_form`), the fit reads the
        table for the Gram and the moments, both about a shift near the
        columns' means (once where the Gram's kernel takes the table: one
        device, a tile of rows and more, 128 to 512 columns; else twice), and
        descends on those (the covariance form:
        the same iterates as recomputing the residual for every coordinate,
        wherever the columns stand).  Otherwise it descends on the table with
        one residual kept up to date (the residual form).  One program either
        way; nothing is read back, ``n_iter`` converts on first access."""
        if not isinstance(x, DNDarray) or not isinstance(y, DNDarray):
            raise TypeError("x and y need to be DNDarrays")
        if x.ndim != 2:
            raise ValueError(f"x needs to be 2D, got {x.ndim}D")
        n, f = x.shape
        dtype = x.dtype.jax_type() if types.heat_type_is_inexact(x.dtype) else jnp.float32
        gram = _gram_form(n, f, dtype)
        with _span("ht.regression.Lasso.fit", rows=n, features=f, split=x.split, max_iter=self.max_iter,
                   form="gram" if gram else "residual") as root:
            # reads of the table a fit: the kernel's one for the Gram and the
            # moments both, else the Gram's and the moments', or the sums of
            # squares' and a column a turn and the residual a sweep
            one = _one_read(n, f, dtype, x.comm.size == 1)
            root.attrs.update(passes=(1 if one else 2) if gram else 1 + 2 * self.max_iter)
            self._fit(x, y, gram)
        return self

    def _fit(self, x: DNDarray, y: DNDarray, gram: bool) -> None:
        n = x.shape[0]
        over_mesh = x.split == 0 and x.comm.size > 1
        # over a mesh the rows as they lie, the padding zeroed: it adds nothing to any sum
        xd = x._masked(0) if over_mesh else x._dense()
        if not types.heat_type_is_inexact(x.dtype):
            xd = xd.astype(jnp.float32)
        yd = y._masked(0) if over_mesh and y.split == 0 else y._dense()
        if over_mesh and yd.shape[0] != xd.shape[0]:
            yd = jnp.pad(yd, [(0, xd.shape[0] - yd.shape[0])] + [(0, 0)] * (yd.ndim - 1))
        plan = dict(n=n, gram=gram, syrk_ok=x.comm.size == 1, comm=x.comm if over_mesh else None)
        lam, tol = self.__lam, self.tol
        if self.checkpoint_every is not None or self.resume_from is not None:
            # chunked checkpoint/resume path: what the sweeps run on is made
            # once, then the same sweep sequence as the single-launch fit,
            # theta checkpointed (and NaN-guarded) every checkpoint_every sweeps
            from ..core.base import resumable_fit_loop

            with _span("lasso.loop", chunked=True):
                def part(phase, state=None, sweeps=0):
                    dispatch.record_external_dispatch()
                    return _program(xd, yd, () if phase == "prepare" else made, lam, tol, state, max_iter=sweeps,
                                    phase=phase, **plan)

                made = part("prepare")
                # the checkpoints hold theta as the sweeps hold it (`_enter`): a chunk continues where the last ended, to the bit
                state, it = resumable_fit_loop(
                    lambda state, n_sweeps: part("descend", state, n_sweeps),
                    lambda: part("enter"),
                    self.max_iter,
                    float(self.tol),
                    checkpoint_every=self.checkpoint_every,
                    checkpoint_dir=self.checkpoint_dir,
                    resume_from=self.resume_from,
                    site="lasso.iter",
                    what="theta",
                    converged_when=lambda s, t: s < t,  # cd cond: delta >= tol continues
                )
                theta = part("leave", jnp.asarray(state, xd.dtype))
        else:
            # one launch for the whole fit, the passes and the sweeps — the
            # same dispatch-amortization shape as the kmeans Lloyd loop
            with _span("lasso.loop", chunked=False):
                dispatch.record_external_dispatch()
                theta, it, _ = _lasso_fit(xd, yd, lam, tol, None, max_iter=self.max_iter, **plan)
        self._n_iter = it  # lazy: n_iter converts on first access
        self.__theta = DNDarray.from_dense(theta.reshape(-1, 1), None, x.device, x.comm)

    def predict(self, x: DNDarray) -> DNDarray:
        """Linear prediction with intercept (lasso.py:200)."""
        if self.__theta is None:
            raise RuntimeError("fit needs to be called before predict")
        xd = x._dense()
        if not types.heat_type_is_inexact(x.dtype):
            xd = xd.astype(jnp.float32)
        th = self.__theta._dense().ravel()
        yest = dispatch.eager_apply(_linear_predict_op, (xd, th))
        return DNDarray.from_dense(yest, x.split, x.device, x.comm)
