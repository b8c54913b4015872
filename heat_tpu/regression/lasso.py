"""Lasso regression, analog of heat/regression/lasso.py (lasso.py:10).

Coordinate descent with soft thresholding; every inner product is a
distributed dot over the sharded sample axis (an MXU matvec + psum).
"""

from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp

from functools import partial

from ..core import dispatch, types
from ..core.base import BaseEstimator, RegressionMixin, lazy_scalar_property
from ..core.dndarray import DNDarray


def _linear_predict_op(xd, th):
    """Intercept + coefficients in one cached program (the predict hot
    path the serving layer batches)."""
    yest = jnp.matmul(xd, th[1:], precision=jax.lax.Precision.HIGHEST) + th[0]
    return yest.reshape(-1, 1)


def _soft_threshold_op(d, *, lam):
    return jnp.sign(d) * jnp.maximum(jnp.abs(d) - lam, 0.0)


@partial(jax.jit, static_argnames=("max_iter",))
def _cd_loop(X, yd, col_sq, lam, tol, max_iter, theta0):
    """Whole cyclic-coordinate-descent fit as one on-device while_loop.

    A host-side sweep loop costs a device->host sync per sweep;
    lam/tol are traced so a regularization-path sweep (examples/lasso) reuses one compiled executable.
    ``theta0`` is the starting iterate (zeros for a fresh fit; a restored
    checkpoint for the resumable path — the sweep sequence continues
    exactly where it stopped).  Returns (theta, sweeps_run, last_delta).
    """
    m = X.shape[1]
    hp = jax.lax.Precision.HIGHEST

    def one_sweep(th):
        def body(j, t):
            resid = yd - jnp.matmul(X, t, precision=hp) + X[:, j] * t[j]
            rho = jnp.matmul(X[:, j], resid, precision=hp)
            new_j = jnp.where(
                j == 0,
                rho / jnp.maximum(col_sq[0], 1e-30),  # intercept not penalized
                (jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - lam, 0.0))
                / jnp.maximum(col_sq[j], 1e-30),
            )
            return t.at[j].set(new_j)

        return jax.lax.fori_loop(0, m, body, th)

    def cond(carry):
        th, it, delta = carry
        return jnp.logical_and(it < max_iter, delta >= tol)

    def body(carry):
        th, it, _ = carry
        new = one_sweep(th)
        delta = jnp.max(jnp.abs(new - th)).astype(jnp.float32)
        return new, it + 1, delta

    init = (jnp.asarray(theta0, X.dtype), jnp.int32(0), jnp.asarray(jnp.inf, jnp.float32))
    theta, it, delta = jax.lax.while_loop(cond, body, init)
    return theta, it, delta

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
        """Cyclic coordinate descent (lasso.py:120)."""
        if not isinstance(x, DNDarray) or not isinstance(y, DNDarray):
            raise TypeError("x and y need to be DNDarrays")
        if x.ndim != 2:
            raise ValueError(f"x needs to be 2D, got {x.ndim}D")
        xd = x._dense()
        if not types.heat_type_is_inexact(x.dtype):
            xd = xd.astype(jnp.float32)
        yd = y._dense().reshape(-1).astype(xd.dtype)
        n, f = xd.shape
        # prepend intercept column (lasso.py:135)
        X = jnp.concatenate([jnp.ones((n, 1), xd.dtype), xd], axis=1)
        col_sq = jnp.sum(X * X, axis=0)

        lam = jnp.asarray(self.__lam, xd.dtype)
        tol = jnp.asarray(self.tol, jnp.float32)
        if self.checkpoint_every is not None or self.resume_from is not None:
            # chunked checkpoint/resume path: same sweep sequence as the
            # single-launch fit, theta checkpointed (and NaN-guarded)
            # every checkpoint_every sweeps
            from ..core.base import resumable_fit_loop

            def run_chunk(theta, n_sweeps):
                dispatch.record_external_dispatch()
                return _cd_loop(X, yd, col_sq, lam, tol, n_sweeps, theta)

            theta, it = resumable_fit_loop(
                run_chunk,
                lambda: jnp.zeros((X.shape[1],), X.dtype),
                self.max_iter,
                float(self.tol),
                checkpoint_every=self.checkpoint_every,
                checkpoint_dir=self.checkpoint_dir,
                resume_from=self.resume_from,
                site="lasso.iter",
                what="theta",
                converged_when=lambda s, t: s < t,  # cd cond: delta >= tol continues
            )
            theta = jnp.asarray(theta, X.dtype)
        else:
            # one launch for the whole coordinate-descent fit — the same
            # dispatch-amortization shape as the kmeans Lloyd loop
            dispatch.record_external_dispatch()
            theta, it, _ = _cd_loop(
                X, yd, col_sq, lam, tol, self.max_iter,
                jnp.zeros((X.shape[1],), X.dtype),
            )
        self._n_iter = it  # lazy: n_iter converts on first access
        self.__theta = DNDarray.from_dense(theta.reshape(-1, 1), None, x.device, x.comm)
        return self

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
