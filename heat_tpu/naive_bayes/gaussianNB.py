"""Gaussian naive Bayes, analog of heat/naive_bayes/gaussianNB.py
(gaussianNB.py:13).

Per-class mean/variance come from masked global reductions over the
sharded sample axis; ``partial_fit`` keeps the reference's incremental
moment-merge update (gaussianNB.py:180+).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import types
from ..core.base import BaseEstimator, ClassificationMixin, lazy_scalar_property
from ..core.dndarray import DNDarray

__all__ = ["GaussianNB"]


@jax.jit
def _gnb_update(xd, yd, w, cls_arr, theta, var, counts, eps_applied, var_smoothing):
    """One fused moment-merge update over ALL classes.

    The per-class Python loop this replaces dispatched ~10 eager ops per
    class (hundreds of host dispatches per fit); here the
    class axis is a (n, c) mask matrix and the per-class sums are two
    matmuls.  Within-class variances use the global-mean-shifted data so
    E[x^2]-mu^2 stays numerically benign."""
    var_old = var - eps_applied
    mask = (yd[:, None] == cls_arr[None, :]).astype(xd.dtype) * w[:, None]  # (n, c)
    n_new = mask.sum(axis=0)  # (c,)
    safe = jnp.maximum(n_new, 1e-30)
    xbar = jnp.mean(xd, axis=0)
    xc = xd - xbar[None, :]
    mu_c = (mask.T @ xc) / safe[:, None]  # (c, f), in shifted coords
    ex2_c = (mask.T @ (xc * xc)) / safe[:, None]
    var_new = jnp.maximum(ex2_c - mu_c**2, 0.0)
    mu_new = mu_c + xbar[None, :]

    n_old = counts
    n_tot = n_old + n_new
    safe_tot = jnp.maximum(n_tot, 1e-30)
    mu_tot = (n_old[:, None] * theta + n_new[:, None] * mu_new) / safe_tot[:, None]
    # merged second moment (gaussianNB.py ~_update_mean_variance)
    ssd = (
        n_old[:, None] * var_old
        + n_new[:, None] * var_new
        + ((n_old * n_new / safe_tot)[:, None]) * (theta - mu_new) ** 2
    )
    var_tot = ssd / safe_tot[:, None]
    keep = (n_tot > 0)[:, None]
    theta_out = jnp.where(keep, mu_tot, theta)
    var_out = jnp.where(keep, var_tot, var_old)
    eps = var_smoothing * jnp.max(jnp.var(xd, axis=0))
    return theta_out, var_out + eps, n_tot, eps


class GaussianNB(BaseEstimator, ClassificationMixin):
    """Gaussian likelihood naive Bayes classifier (gaussianNB.py:13)."""

    def __init__(self, priors: Optional[DNDarray] = None, var_smoothing: float = 1e-9):
        self.priors = priors
        self.var_smoothing = var_smoothing
        self.classes_ = None
        self.theta_ = None
        self.var_ = None
        self.class_count_ = None
        self.class_prior_ = None
        self._epsilon = None

    sigma_ = property(lambda self: self.var_)  # alias kept by the reference

    # fits store the device scalar so partial_fit never blocks on a
    # device->host sync; the host conversion happens (once) on first access
    epsilon_ = lazy_scalar_property("_epsilon", float)

    def fit(self, x: DNDarray, y: DNDarray, sample_weight: Optional[DNDarray] = None) -> "GaussianNB":
        """Estimate per-class Gaussian parameters (gaussianNB.py:120)."""
        self.classes_ = None
        self.theta_ = None
        return self.partial_fit(x, y, classes=None, sample_weight=sample_weight)

    def partial_fit(
        self,
        x: DNDarray,
        y: DNDarray,
        classes: Optional[DNDarray] = None,
        sample_weight: Optional[DNDarray] = None,
    ) -> "GaussianNB":
        """Incremental fit on a batch (gaussianNB.py:180), merging moments
        with the reference's count-weighted update."""
        if not isinstance(x, DNDarray) or not isinstance(y, DNDarray):
            raise TypeError("x and y need to be DNDarrays")
        if x.ndim != 2:
            raise ValueError(f"expected x to be 2D, got {x.ndim}D")
        xd = x._dense()
        if not types.heat_type_is_inexact(x.dtype):
            xd = xd.astype(jnp.float32)
        yd = y._dense().reshape(-1)  # native dtype: labels may be floats or wide ints
        if sample_weight is not None:
            w = sample_weight._dense().reshape(-1).astype(xd.dtype)
        else:
            w = jnp.ones((xd.shape[0],), xd.dtype)

        if self.classes_ is None:
            if classes is not None:
                cls = np.asarray(classes._dense() if isinstance(classes, DNDarray) else classes)
            else:
                cls = np.unique(np.asarray(yd))
            self.classes_ = DNDarray.from_dense(jnp.asarray(cls), None, x.device, x.comm)
            n_cls = len(cls)
            n_feat = xd.shape[1]
            self.theta_ = jnp.zeros((n_cls, n_feat), xd.dtype)
            self.var_ = jnp.zeros((n_cls, n_feat), xd.dtype)
            self.class_count_ = jnp.zeros((n_cls,), xd.dtype)

        cls_arr = self.classes_._dense()

        theta = jnp.asarray(self.theta_) if not isinstance(self.theta_, DNDarray) else self.theta_._dense()
        var = jnp.asarray(self.var_) if not isinstance(self.var_, DNDarray) else self.var_._dense()
        counts = jnp.asarray(self.class_count_) if not isinstance(self.class_count_, DNDarray) else self.class_count_._dense()
        eps_applied = getattr(self, "_eps_applied", None)
        if eps_applied is None:
            eps_applied = jnp.zeros((), xd.dtype)

        theta_n, var_n, counts_n, eps = _gnb_update(
            xd, yd, w, cls_arr.astype(yd.dtype), theta, var, counts,
            eps_applied, float(self.var_smoothing),
        )
        # the smoothing term stays a lazy device scalar: no host sync per
        # partial_fit (it is removed before the next merge, see _gnb_update)
        self._epsilon = eps
        self._eps_applied = eps
        if self.priors is not None:
            pri = self.priors._dense() if isinstance(self.priors, DNDarray) else jnp.asarray(self.priors)
        else:
            pri = counts_n / jnp.maximum(jnp.sum(counts_n), 1e-30)

        # public attributes are DNDarrays (reference parity)
        wrap = lambda a: DNDarray.from_dense(a, None, x.device, x.comm)
        self.theta_ = wrap(theta_n)
        self.var_ = wrap(var_n)
        self.class_count_ = wrap(counts_n)
        self.class_prior_ = wrap(pri)
        return self

    def _joint_log_likelihood(self, x: DNDarray) -> jnp.ndarray:
        """Per-class joint log likelihood (gaussianNB.py:320)."""
        xd = x._dense()
        if not types.heat_type_is_inexact(x.dtype):
            xd = xd.astype(jnp.float32)
        theta = self.theta_._dense() if isinstance(self.theta_, DNDarray) else jnp.asarray(self.theta_)
        var = self.var_._dense() if isinstance(self.var_, DNDarray) else jnp.asarray(self.var_)
        prior_a = (
            self.class_prior_._dense()
            if isinstance(self.class_prior_, DNDarray)
            else jnp.asarray(self.class_prior_)
        )
        # all classes at once with the quadratic form expanded into three
        # matmul-shaped terms: peak memory stays (n, c) instead of the
        # (n, c, f) broadcast tensor, and the contractions ride the MXU
        prior = jnp.log(jnp.maximum(prior_a, 1e-30))  # (c,)
        norm = -0.5 * jnp.sum(jnp.log(2.0 * jnp.pi * var), axis=1)  # (c,)
        hi = jax.lax.Precision.HIGHEST
        inv_var = 1.0 / var  # (c, f)
        t1 = jnp.matmul(xd * xd, inv_var.T, precision=hi)  # (n, c)
        t2 = jnp.matmul(xd, (theta * inv_var).T, precision=hi)  # (n, c)
        t3 = jnp.sum(theta * theta * inv_var, axis=1)  # (c,)
        quad = -0.5 * (t1 - 2.0 * t2 + t3[None, :])
        return prior[None, :] + norm[None, :] + quad

    def predict(self, x: DNDarray) -> DNDarray:
        """Most probable class per sample (gaussianNB.py:360)."""
        if self.theta_ is None:
            raise RuntimeError("fit needs to be called before predict")
        jll = self._joint_log_likelihood(x)
        cls = self.classes_._dense()
        pred = cls[jnp.argmax(jll, axis=1)]
        return DNDarray.from_dense(pred, x.split, x.device, x.comm)

    def predict_proba(self, x: DNDarray) -> DNDarray:
        """Class probabilities (gaussianNB.py:390)."""
        jll = self._joint_log_likelihood(x)
        log_prob = jll - jax_logsumexp(jll, axis=1, keepdims=True)
        return DNDarray.from_dense(jnp.exp(log_prob), x.split, x.device, x.comm)

    def predict_log_proba(self, x: DNDarray) -> DNDarray:
        jll = self._joint_log_likelihood(x)
        return DNDarray.from_dense(jll - jax_logsumexp(jll, axis=1, keepdims=True), x.split, x.device, x.comm)


def jax_logsumexp(a, axis=None, keepdims=False):
    from jax.scipy.special import logsumexp

    return logsumexp(a, axis=axis, keepdims=keepdims)
