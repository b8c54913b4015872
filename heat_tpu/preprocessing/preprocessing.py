"""Data scalers, analog of heat/preprocessing/preprocessing.py
(StandardScaler :49, MinMaxScaler :158, Normalizer :284, MaxAbsScaler
:358, RobustScaler :444).  All are pure compositions of the distributed
ops layer (mean/var/min/max/percentile over the sharded sample axis).

``copy`` means what upstream means by it.  With ``copy=True`` (the default)
``transform`` / ``inverse_transform`` / ``fit_transform`` return a new array
and leave their input untouched.  With ``copy=False`` they build the same
chain (``(x - mean) / scale``), hand it to the input through the library's
one in-place store (``dndarray._iop``) and return that same object.  The
store waits for the input's first reader (``docs/dispatch.md``, "The deferred
in-place store"): a transform and its inverse, or several scalers in a row,
are then ONE program, one read and one write of the table, the input's buffer
donated where it is provably unshared (``dispatch.cast_store``), so a table
that fills the chip is scaled without a second generation of it.  Where the
buffer is shared (a second ``DNDarray`` on it, a held ``larray_padded``) the
result is still right and the store does not donate.  An input that has to be
cast first (integers) cannot be written in place: the store's cast check
raises ``TypeError`` at the call, as upstream's does.

Every ``fit`` / ``transform`` / ``inverse_transform`` is one root span
``ht.preprocessing.<Class>.<method>`` (``docs/observability.md``).
"""

from __future__ import annotations

import contextlib
from typing import Tuple

import jax
import jax.numpy as jnp

from ..core import arithmetics, dispatch, exponential, rounding, statistics, types
from ..core._operations import __local_op as _local_op
from ..core.base import BaseEstimator, TransformMixin
from ..core.dndarray import DNDarray, _iop
from ..telemetry.spans import span as _span

__all__ = ["StandardScaler", "MinMaxScaler", "Normalizer", "MaxAbsScaler", "RobustScaler"]


def _check_2d_float(x, name="X"):
    if not isinstance(x, DNDarray):
        raise TypeError(f"{name} must be a DNDarray, got {type(x)}")
    if not types.heat_type_is_inexact(x.dtype):
        return x.astype(types.float32)
    return x


def _one_where_zero(a):
    return jnp.where(a == 0, jnp.ones((), a.dtype), a)


def _guard_zero(x: DNDarray) -> DNDarray:
    """Zeros replaced by ones: a constant feature, or an empty sample, is
    left as it is and not divided by zero (preprocessing.py:120)."""
    return _local_op(_one_where_zero, x)


@contextlib.contextmanager
def _root(scaler, method: str, x):
    """The root span of one scaler call and the scope its programs are
    traced under.  Set at exit: ``launches`` (programs enqueued),
    ``stores`` (in-place stores that ran inside the call), ``donations``
    (of them, those that took the buffer), ``deferred`` (in-place stores
    handed to the input to run at its first read) and ``inplace`` (there
    was a store or a deferral, and every store that ran donated)."""
    before = dispatch.cache_stats()
    shape = getattr(x, "shape", ())
    with _span(f"ht.preprocessing.{type(scaler).__name__}.{method}",
               rows=shape[0] if shape else None, features=shape[1] if len(shape) > 1 else None,
               split=getattr(x, "split", None), copy=scaler.copy) as sp:
        with jax.named_scope("scaler.fit" if method == "fit" else "scaler.apply"):
            yield
        after = dispatch.cache_stats()
        stores, donations, deferred = (after[k] - before[k] for k in ("stores", "donations", "deferred_stores"))
        sp.attrs.update(
            launches=sum(after[k] - before[k] for k in ("dispatches", "external_dispatches")),
            stores=stores, donations=donations, deferred=deferred,
            inplace=0 < stores + deferred and stores == donations)


class _Scaler(BaseEstimator, TransformMixin):
    """What the five scalers share: the spans, and where a result goes."""

    def _result(self, x: DNDarray, y: DNDarray) -> DNDarray:
        """``y`` itself, or with ``copy=False`` the input holding it: ONE
        in-place store of the pending chain, which waits for its reader."""
        return y if self.copy else _iop(x, y)

    def fit(self, x: DNDarray, *args, **kwargs):
        with _root(self, "fit", x):
            self._fit(_check_2d_float(x), *args, **kwargs)
        return self

    def transform(self, x: DNDarray) -> DNDarray:
        with _root(self, "transform", x):
            return self._result(x, self._transform(_check_2d_float(x)))

    def inverse_transform(self, y: DNDarray) -> DNDarray:
        with _root(self, "inverse_transform", y):
            return self._result(y, self._inverse(_check_2d_float(y, "Y")))


class StandardScaler(_Scaler):
    """Zero-mean unit-variance standardization (preprocessing.py:49)."""

    def __init__(self, copy: bool = True, with_mean: bool = True, with_std: bool = True):
        self.copy = copy
        self.with_mean = with_mean
        self.with_std = with_std
        self.mean_ = None
        self.var_ = None

    def _fit(self, x: DNDarray, sample_weight=None) -> None:
        if sample_weight is not None:
            raise NotImplementedError("sample_weight is not yet supported (matching preprocessing.py:95)")
        if not self.with_std:
            self.mean_, self.var_ = statistics.mean(x, axis=0) if self.with_mean else None, None
            return
        # both moments from ONE program, one read of the table (statistics.mean_var)
        mean, var = statistics.mean_var(x, axis=0)
        self.mean_ = mean if self.with_mean else None
        # zero-variance features are guarded (preprocessing.py:120)
        self.var_ = _guard_zero(var)

    def _transform(self, x: DNDarray) -> DNDarray:
        if self.with_mean and self.mean_ is not None:
            x = x - self.mean_
        if self.with_std and self.var_ is not None:
            x = x / exponential.sqrt(self.var_)
        return x

    def _inverse(self, y: DNDarray) -> DNDarray:
        if self.with_std and self.var_ is not None:
            y = y * exponential.sqrt(self.var_)
        if self.with_mean and self.mean_ is not None:
            y = y + self.mean_
        return y


class MinMaxScaler(_Scaler):
    """Rescale features to a range (preprocessing.py:158)."""

    def __init__(self, feature_range: Tuple[float, float] = (0.0, 1.0), copy: bool = True, clip: bool = False):
        if feature_range[0] >= feature_range[1]:
            raise ValueError(f"Minimum of desired feature range must be smaller than maximum, got {feature_range}")
        self.feature_range = feature_range
        self.copy = copy
        self.clip = clip
        self.data_min_ = None
        self.data_max_ = None
        self.scale_ = None
        self.min_ = None

    def _fit(self, x: DNDarray) -> None:
        # both extrema from ONE program, one read of the table (statistics.min_max)
        self.data_min_, self.data_max_ = statistics.min_max(x, axis=0)
        lo, hi = self.feature_range
        self.scale_ = (hi - lo) / _guard_zero(self.data_max_ - self.data_min_)
        self.min_ = lo - self.data_min_ * self.scale_

    def _transform(self, x: DNDarray) -> DNDarray:
        y = x * self.scale_ + self.min_
        if self.clip:
            y = rounding.clip(y, self.feature_range[0], self.feature_range[1])
        return y

    def _inverse(self, y: DNDarray) -> DNDarray:
        return (y - self.min_) / self.scale_


class Normalizer(_Scaler):
    """Scale each sample to unit norm (preprocessing.py:284)."""

    def __init__(self, norm: str = "l2", copy: bool = True):
        if norm not in ("l1", "l2", "max"):
            raise NotImplementedError(f"norm must be 'l1', 'l2' or 'max', got {norm!r}")
        self.norm = norm
        self.copy = copy

    def _fit(self, x: DNDarray) -> None:
        pass  # stateless (preprocessing.py:320)

    def _transform(self, x: DNDarray) -> DNDarray:
        if self.norm == "l2":
            n = exponential.sqrt(arithmetics.sum(x * x, axis=1, keepdims=True))
        elif self.norm == "l1":
            n = arithmetics.sum(rounding.abs(x), axis=1, keepdims=True)
        else:
            n = statistics.max(rounding.abs(x), axis=1, keepdims=True)
        return x / _guard_zero(n)


class MaxAbsScaler(_Scaler):
    """Scale by the per-feature maximum absolute value (preprocessing.py:358)."""

    def __init__(self, copy: bool = True):
        self.copy = copy
        self.max_abs_ = None
        self.scale_ = None

    def _fit(self, x: DNDarray) -> None:
        self.max_abs_ = statistics.max(rounding.abs(x), axis=0)
        self.scale_ = _guard_zero(self.max_abs_)

    def _transform(self, x: DNDarray) -> DNDarray:
        return x / self.scale_

    def _inverse(self, y: DNDarray) -> DNDarray:
        return y * self.scale_


class RobustScaler(_Scaler):
    """Median/IQR scaling (preprocessing.py:444)."""

    def __init__(
        self,
        quantile_range: Tuple[float, float] = (25.0, 75.0),
        copy: bool = True,
        with_centering: bool = True,
        with_scaling: bool = True,
        unit_variance: bool = False,
    ):
        if unit_variance:
            raise NotImplementedError("unit_variance is not yet supported (matching preprocessing.py:500)")
        lo, hi = quantile_range
        if not 0 <= lo <= hi <= 100:
            raise ValueError(f"Invalid quantile range: {quantile_range}")
        self.quantile_range = quantile_range
        self.copy = copy
        self.with_centering = with_centering
        self.with_scaling = with_scaling
        self.unit_variance = unit_variance
        self.center_ = None
        self.iqr_ = None

    def _fit(self, x: DNDarray) -> None:
        # the median and the range's two quantiles in ONE call: one pass
        # over the table serves all three (statistics.percentile)
        q = ([50.0] if self.with_centering else []) + (list(self.quantile_range) if self.with_scaling else [])
        if not q:
            return
        got = statistics.percentile(x, q, axis=0)
        if self.with_centering:
            self.center_ = got[0]
        if self.with_scaling:
            self.iqr_ = _guard_zero(got[len(q) - 1] - got[len(q) - 2])

    def _transform(self, x: DNDarray) -> DNDarray:
        if self.with_centering and self.center_ is not None:
            x = x - self.center_
        if self.with_scaling and self.iqr_ is not None:
            x = x / self.iqr_
        return x

    def _inverse(self, y: DNDarray) -> DNDarray:
        if self.with_scaling and self.iqr_ is not None:
            y = y * self.iqr_
        if self.with_centering and self.center_ is not None:
            y = y + self.center_
        return y
