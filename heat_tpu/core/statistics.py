"""Statistical operations, analog of heat/core/statistics.py.

The reference's distributed machinery — custom MPI ops for argmax/argmin
(statistics.py:1372-1442), pairwise moment merging for var/skew/kurtosis
(``__merge_moments`` :1077), and the distributed-sort percentile (:1443) —
is replaced by global jnp reductions/sorts over sharded arrays: XLA emits
the same (val, idx) pair reductions and merge trees.  The remaining
distribution logic is pad masking with per-op neutral elements and output
split bookkeeping.
"""

from __future__ import annotations

import builtins
import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as _P

from ..telemetry.spans import span as _span
from . import dispatch, kernels, types
from ._operations import __binary_op as _binary_op
from ._operations import __reduce_op as _reduce_op
from ._operations import _reduced_shape, _reduced_split, _wrap_reduced
from .dndarray import DNDarray
from .stride_tricks import sanitize_axis

__all__ = [
    "argmax",
    "argmin",
    "average",
    "bincount",
    "bucketize",
    "cov",
    "digitize",
    "histc",
    "histogram",
    "kurtosis",
    "max",
    "maximum",
    "mean",
    "median",
    "min",
    "minimum",
    "percentile",
    "skew",
    "std",
    "var",
]


def _dense_reduce(fn, x: DNDarray, axis, keepdims: bool = False, force_int64=False) -> DNDarray:
    """Apply a jnp reduction on the dense view and re-wrap with the
    reduced split (helper for ops whose masking would be fiddly).

    A module-level ``fn`` marked ``_dispatch_cacheable`` routes through
    the executable cache (stable op identity -> stable cache key); the
    per-call lambdas other reductions pass stay eager — caching those
    would mint a fresh key (and a fresh XLA compile) per call."""
    axis_s = sanitize_axis(x.shape, axis)
    axes = tuple(range(x.ndim)) if axis_s is None else (axis_s if isinstance(axis_s, tuple) else (axis_s,))
    if getattr(fn, "_dispatch_cacheable", False):
        from . import dispatch

        kd_axis = tuple(axis_s) if isinstance(axis_s, list) else axis_s
        result = dispatch.eager_apply(
            fn, (x._dense(),), {"axis": kd_axis, "keepdims": bool(keepdims)}
        )
    else:
        result = fn(x._dense(), axis_s, keepdims)
    if x.split is None:
        out_split = None
    elif x.split in axes:
        out_split = None
    else:
        out_split = _reduced_split(x.split, axes, keepdims, reduced=False)
    if result.ndim == 0:
        out_split = None
    return DNDarray.from_dense(result, out_split, x.device, x.comm)


def _argmax_fn(a, axis=None, keepdims=False):
    return jnp.argmax(a, axis=axis, keepdims=keepdims).astype(
        types.canonical_dtype(jnp.int64)
    )


def _argmin_fn(a, axis=None, keepdims=False):
    return jnp.argmin(a, axis=axis, keepdims=keepdims).astype(
        types.canonical_dtype(jnp.int64)
    )


# stable module-level identity -> one executable-cache entry per shape;
# argmin/argmax sit on the KMeans-family predict hot path the serving
# layer batches, where an eager launch per request is the difference
# between a cache hit and a fresh dispatch
_argmax_fn._dispatch_cacheable = True
_argmin_fn._dispatch_cacheable = True


def argmax(x, axis=None, out=None, keepdims=False, **kwargs):
    """Index of the maximum (statistics.py:33; distributed via custom
    MPI_ARGMAX in the reference, a plain global argmax here)."""
    res = _dense_reduce(_argmax_fn, x, axis, keepdims)
    return _to_out(res, out)


def argmin(x, axis=None, out=None, keepdims=False, **kwargs):
    """Index of the minimum (statistics.py:119)."""
    res = _dense_reduce(_argmin_fn, x, axis, keepdims)
    return _to_out(res, out)


def _to_out(res: DNDarray, out: Optional[DNDarray]) -> DNDarray:
    if out is None:
        return res
    from .sanitation import store_out

    return store_out(res, out)


def average(x, axis=None, weights=None, returned=False):
    """Weighted average (statistics.py:205)."""
    from . import arithmetics

    if weights is None:
        result = mean(x, axis)
        if returned:
            axes = tuple(range(x.ndim)) if axis is None else (
                axis if isinstance(axis, tuple) else (sanitize_axis(x.shape, axis),)
            )
            cnt = 1
            for a in axes:
                cnt *= x.shape[a]
            from . import factories

            return result, factories.full(result.shape, cnt, dtype=types.float32, split=result.split)
        return result
    if not isinstance(weights, DNDarray):
        from . import factories

        weights = factories.array(weights)
    if axis is None:
        if weights.shape != x.shape:
            raise TypeError("Axis must be specified when shapes of x and weights differ.")
        wsum = arithmetics.sum(weights)
        result = arithmetics.sum(arithmetics.mul(x, weights)) / wsum
    else:
        axis_s = sanitize_axis(x.shape, axis)
        if weights.ndim == 1 and weights.shape[0] == x.shape[axis_s]:
            bshape = [1] * x.ndim
            bshape[axis_s] = weights.shape[0]
            wdense = weights._dense().reshape(bshape)
            from . import factories

            weights = factories.array(wdense, comm=x.comm)
        wsum = arithmetics.sum(weights, axis=axis_s)
        result = arithmetics.sum(arithmetics.mul(x, weights), axis=axis_s) / wsum
    if returned:
        if wsum.shape != result.shape:
            from . import manipulations

            wsum = manipulations.broadcast_to(wsum, result.shape)
        return result, wsum
    return result


def bincount(x, weights=None, minlength: int = 0):
    """Count occurrences of non-negative ints (statistics.py:379)."""
    if x.ndim != 1:
        raise ValueError("bincount requires a 1-D input")
    w = weights._dense() if isinstance(weights, DNDarray) else weights
    dense = x._dense()
    if dense.shape[0] == 0:
        length = minlength
    else:
        length = builtins_max(int(jnp.max(dense)) + 1, minlength) if dense.size else minlength
    result = jnp.bincount(dense, weights=w, minlength=minlength, length=length)
    return DNDarray.from_dense(result, x.split if x.split is not None else None, x.device, x.comm)


def builtins_max(a, b):
    return a if a > b else b


def bucketize(input, boundaries, out_int32: bool = False, right: bool = False, out=None):
    """Bucket index of each element (statistics.py:443)."""
    b = boundaries._dense() if isinstance(boundaries, DNDarray) else jnp.asarray(boundaries)
    side = "left" if right else "right"
    result = jnp.searchsorted(b, input._dense(), side=side)
    result = result.astype(jnp.int32 if out_int32 else types.canonical_dtype(jnp.int64))
    res = DNDarray.from_dense(result, input.split, input.device, input.comm)
    return _to_out(res, out)


def cov(m, y=None, rowvar: bool = True, bias: bool = False, ddof: Optional[int] = None):
    """Covariance matrix estimate (statistics.py:518)."""
    if not isinstance(m, DNDarray):
        raise TypeError(f"m must be a DNDarray, got {type(m)}")
    if m.ndim > 2:
        raise ValueError("m has more than 2 dimensions")
    if ddof is not None and not isinstance(ddof, int):
        raise TypeError("ddof must be integer")
    x = m._dense()
    yd = y._dense() if isinstance(y, DNDarray) else y
    result = jnp.cov(x, yd, rowvar=rowvar, bias=bias, ddof=ddof)
    split = 0 if m.split is not None and result.ndim > 0 else None
    return DNDarray.from_dense(jnp.atleast_2d(result) if result.ndim == 2 else result, split, m.device, m.comm)


def digitize(x, bins, right: bool = False):
    """Bin index of each element, numpy semantics (statistics.py:613)."""
    b = bins._dense() if isinstance(bins, DNDarray) else jnp.asarray(bins)
    result = jnp.digitize(x._dense(), b, right=right)
    return DNDarray.from_dense(result, x.split, x.device, x.comm)


def histc(input, bins: int = 100, min: float = 0.0, max: float = 0.0, out=None):
    """Histogram with equal-width bins (statistics.py:687)."""
    dense = input._dense().ravel()
    if min == 0.0 and max == 0.0:
        lo = jnp.min(dense)
        hi = jnp.max(dense)
    else:
        lo, hi = min, max
        dense = dense[(dense >= lo) & (dense <= hi)]
    hist, _ = jnp.histogram(dense, bins=bins, range=(float(lo), float(hi)))
    res = DNDarray.from_dense(hist.astype(input.dtype.jax_type()), None, input.device, input.comm)
    return _to_out(res, out)


def histogram(a, bins=10, range=None, weights=None, density=None):
    """NumPy-style histogram (statistics.py:741)."""
    dense = a._dense().ravel()
    w = weights._dense().ravel() if isinstance(weights, DNDarray) else weights
    b = bins._dense() if isinstance(bins, DNDarray) else bins
    hist, edges = jnp.histogram(dense, bins=b, range=range, weights=w, density=density)
    return (
        DNDarray.from_dense(hist, None, a.device, a.comm),
        DNDarray.from_dense(edges, None, a.device, a.comm),
    )


def kurtosis(x, axis=None, unbiased: bool = True, Fisher: bool = True):
    """Kurtosis (4th standardized moment; statistics.py:787; distributed
    moment merging in the reference is a plain global moment here)."""
    m4 = _central_moment(x, 4, axis)
    v = var(x, axis, ddof=0)
    from . import arithmetics

    g2 = m4 / (v * v)
    if unbiased:
        n = _axis_count(x, axis)
        g2_d = g2._dense()
        k = ((n - 1) / ((n - 2) * (n - 3))) * ((n + 1) * g2_d - 3 * (n - 1)) + 3
        g2 = DNDarray.from_dense(k, g2.split, g2.device, g2.comm)
    if Fisher:
        g2 = g2 - 3.0
    return g2


def _axis_count(x: DNDarray, axis) -> float:
    if axis is None:
        return float(x.size)
    axis_s = sanitize_axis(x.shape, axis)
    axes = axis_s if isinstance(axis_s, tuple) else (axis_s,)
    n = 1.0
    for a in axes:
        n *= x.shape[a]
    return n


def _central_moment(x: DNDarray, p: int, axis) -> DNDarray:
    mu = mean(x, axis)
    axis_s = sanitize_axis(x.shape, axis)
    dense = x._dense().astype(jnp.float32 if not types.heat_type_is_inexact(x.dtype) else x.dtype.jax_type())
    if axis_s is None:
        dev = dense - mu._dense()
        m = jnp.mean(dev**p)
        return DNDarray.from_dense(m, None, x.device, x.comm)
    mu_d = jnp.expand_dims(mu._dense(), axis_s)
    m = jnp.mean((dense - mu_d) ** p, axis=axis_s)
    return DNDarray.from_dense(m, mu.split, x.device, x.comm)


def max(x, axis=None, out=None, keepdims=False):
    """Maximum along axes (statistics.py:853)."""
    return _reduce_op(jnp.max, x, axis, neutral=_min_neutral(x), out=out, keepdims=keepdims)


def maximum(x1, x2, out=None):
    """Element-wise maximum of two arrays (statistics.py:1004)."""
    return _binary_op(jnp.maximum, x1, x2, out)


def mean(x, axis=None, keepdims: bool = False):
    """Arithmetic mean (statistics.py:898).

    The padded entries must not contribute: sum with 0-masked padding and
    divide by the TRUE element count from gshape.
    """
    from . import arithmetics

    if not types.heat_type_is_inexact(x.dtype):
        x = x.astype(types.float32)
    s = arithmetics.sum(x, axis=axis, keepdims=keepdims)
    n = _axis_count(x, axis)
    return s / n


def _percentile_sorted_1d(x, q, interpolation: str):
    """Percentile of a large 1-D split array on the sorted distribution:
    PSRS sort + an O(len(q)) rank selection — the reference's distributed
    sort + fractional-index interpolation (statistics.py:1443-1532),
    instead of gathering the dense array.  None when the gate declines."""
    from .sample_sort import sample_sort_1d, select_global_ranks, supports_sample_sort

    if types.heat_type_is_inexact(x.dtype):
        xf = x
    else:
        # numpy promotes integer input to float64; honor that under x64
        xf = x.astype(types.float64 if jax.config.jax_enable_x64 else types.float32)
    if not supports_sample_sort(xf, 0, False):
        return None
    v, _ = sample_sort_1d(xf)
    n = x.shape[0]
    q_np = np.atleast_1d(np.asarray(q, np.float64))
    pos = q_np / 100.0 * (n - 1)
    lo = np.floor(pos).astype(np.int64)
    hi = np.ceil(pos).astype(np.int64)
    sel = select_global_ranks(v, np.concatenate([lo, hi]))
    # numpy propagates NaN; the pmax in the rank selection does not (an
    # IEEE max against the -inf fill drops it), so detect NaNs directly
    has_nan = jnp.isnan(xf._masked(0.0)).any()
    lo_v, hi_v = sel[: len(q_np)], sel[len(q_np):]
    lo_v = jnp.where(has_nan, jnp.nan, lo_v)
    hi_v = jnp.where(has_nan, jnp.nan, hi_v)
    frac = jnp.asarray(pos - lo, sel.dtype)
    if interpolation == "linear":
        res = lo_v + frac * (hi_v - lo_v)
    elif interpolation == "lower":
        res = lo_v
    elif interpolation == "higher":
        res = hi_v
    elif interpolation == "midpoint":
        res = 0.5 * (lo_v + hi_v)
    elif interpolation == "nearest":
        near = np.rint(pos).astype(np.int64)
        res = jnp.where(jnp.asarray(near == lo), lo_v, hi_v)
    else:
        raise ValueError(f"unknown interpolation {interpolation!r}")
    if np.ndim(q) == 0:
        res = res[0]
    return DNDarray.from_dense(res, None, x.device, x.comm)


# ----------------------------------------------------------------------
# order statistics along one axis without a sorted copy (PERF.md, PR 33)
# ----------------------------------------------------------------------
#: Extent along the reduced axis from which ``percentile`` selects by
#: counting passes; below it the sort (``jnp.percentile``) is the faster
#: route and stays what it was, result for result.  Step 0 on the chip
#: (PERF.md section 6, PR 33; 50 columns, three quantiles, ms): 4,096 rows
#: 0.71 sorted / 0.69 selected, 16,384 rows 1.32 / 0.85, 65,536 rows
#: 4.29 / 1.29, 4,194,304 rows 577 / 27.
_SELECT_MIN_EXTENT = 1 << 14
#: Compares a counting pass carries before they, and not the memory, bind
#: it.  Step 0 (PR 33): a pass over 2^25 x 50 float32 reads 10.7 ms with 1
#: to 6 compares, 14.6 with 9, 18.7 with 12, 22.6 with 15, 36.1 with 21.
_SELECT_COMPARES = 12
#: Bits of the key a counting pass BY GROUP settles, whatever the number of
#: groups: its kernel packs a digit's counters four to a lane, so a group
#: more costs two operations a value and not a compare and a count a pivot.
_GROUP_BITS = 2


def _select_bits(ranks: int) -> int:
    """Bits of the key one counting pass settles (``2**bits - 1`` pivots a
    rank): the most that keeps a pass's compares within
    ``_SELECT_COMPARES``.  One rank takes 3 bits (11 passes of 7 compares),
    two to four ranks 2 bits (16 passes), more 1 bit (32 passes): by step
    0's readings three ranks read 218 ms at 2 bits, 319 at 1, 339 at 3."""
    return next((b for b in (3, 2) if ranks * ((1 << b) - 1) <= _SELECT_COMPARES), 1)


def _order_key(x):
    """The SIGNED integer whose order is the float's: the float's own bits
    for positive values, the lower bits flipped for negative ones.  -0.0
    sorts just under +0.0 and compares equal to it; NaNs lie beyond the
    infinities and are dealt with by their count.  Signed, because that is
    what the chip compares and takes minima of natively: on the unsigned key
    (this one with the top bit flipped: :func:`_offset`) a counting pass of
    nine compares read 13.89 ms for 12.97 and the neighbours' pass 17.14 for
    10.29 (step 0, PERF.md section 6, PR 36).  Three operations a value."""
    if x.dtype not in (jnp.float32, jnp.float64):
        x = x.astype(jnp.float32)  # exact and order-preserving for the narrow floats
    s = jax.lax.bitcast_convert_type(x, jnp.int64 if x.dtype == jnp.float64 else jnp.int32)
    return s ^ ((s >> (8 * s.dtype.itemsize - 1)) & jnp.iinfo(s.dtype).max)


def _offset(k):
    """The signed key of an unsigned one and back: the top bit flipped, the
    bits taken as the other type.  The unsigned form is the one whose bit
    prefixes are in order, which the digits of a selection are settled in."""
    nbits = 8 * k.dtype.itemsize
    if jnp.issubdtype(k.dtype, jnp.signedinteger):
        u = jax.lax.bitcast_convert_type(k, jnp.uint64 if nbits == 64 else jnp.uint32)
        return u ^ u.dtype.type(1 << (nbits - 1))
    return jax.lax.bitcast_convert_type(k ^ k.dtype.type(1 << (nbits - 1)), jnp.int64 if nbits == 64 else jnp.int32)


def _key_value(k, dtype):
    """The float of an unsigned order key (:func:`_offset` of :func:`_order_key`)."""
    nbits = 8 * k.dtype.itemsize
    top = k.dtype.type(1 << (nbits - 1))
    u = jnp.where(k >> (nbits - 1) == 1, k & ~top, ~k)
    return jax.lax.bitcast_convert_type(u, jnp.float64 if nbits == 64 else jnp.float32).astype(dtype)


def _select_ranks(x, axis: int, lows, with_high: bool, valid, all_sum, all_min, group=None):
    """Exact order statistics along ``axis`` by counting passes over the
    order key: for every 0-based rank in ``lows`` the value of that rank
    and, ``with_high``, of the next rank (``keepdims`` form, stacked along a
    new leading axis), plus the count of NaNs.

    A pass compares the keys with ``2**bits - 1`` pivots a rank
    (:func:`_select_bits`) and sums along ``axis``; ``all_sum`` adds the
    counts over the mesh (ONE all-reduce of a (ranks x pivots x columns)
    array a pass).  The digit of a rank's key is the number of its pivots
    that at most ``rank`` elements lie under, so ``ceil(width / bits)``
    passes settle the key, and ONE more pass, one read of the input, finds
    the next rank: the smallest key above it, or the same key where ties
    reach that far.  Beside the input this holds
    O(ranks x pivots x columns): nothing of the input's size is written.
    ``valid`` masks the canonical padding along a split axis (None: no
    padding).

    With ``group=(labels, k)`` the statistics are taken group by group
    (:func:`_select_by_group`, which has the arguments' meaning there and
    returns the groups' sizes as a fourth value): every pass is then a call
    of a Pallas kernel on a packed copy of the table, 17 of them at 2 bits a
    pass and a 32-bit key, and no fusion reads the copy."""
    if group is not None:
        assert x.ndim == 3 and axis == (1, 2) and valid is None and with_high
        return _select_by_group(x, *group, lows, all_sum, all_min)
    key = _order_key(x)
    top = key.dtype.type(jnp.iinfo(key.dtype).max)
    if valid is not None:
        key = jnp.where(valid, key, top)  # padding sorts last, under no pivot
    nbits = 8 * key.dtype.itemsize
    kept = tuple(1 if d == axis else s for d, s in enumerate(x.shape))  # the `keepdims` shape
    k = len(lows)
    ranks = jnp.asarray(lows, jnp.int32).reshape((-1,) + (1,) * x.ndim)
    # every rank's settled bits in ONE array: the pass's reductions then
    # share their operands and the compiler makes them one fusion, one read
    # of the input (a list of arrays a rank read the table three times a pass)
    pre = jnp.zeros((k,) + kept, jnp.uint64 if nbits == 64 else jnp.uint32)

    def count(mask):
        return jnp.sum(mask, axis=axis, keepdims=True, dtype=jnp.int32)

    with jax.named_scope("quantile.count"):
        nans = None
        shift = nbits
        step = _select_bits(k)
        while shift > 0:
            bits = step if shift >= step else shift  # `min` is this module's reduction
            shift -= bits
            digits = range(1, 1 << bits)
            masks = [key < _offset(pre[r] | pre.dtype.type(d << shift)) for r in range(k) for d in digits]
            if nans is None:  # the first pass counts the NaNs too: one reduction of the pass, one all-reduce
                masks.append(jnp.isnan(x) if valid is None else jnp.isnan(x) & valid)
            counts = all_sum(jnp.stack([count(m) for m in masks]))
            if nans is None:
                nans, counts = counts[-1], counts[:-1]
            under = counts.reshape((k, len(digits)) + kept) <= ranks[:, None]
            pre = pre | (jnp.sum(under, axis=1).astype(pre.dtype) << shift)
        if not with_high:
            return _key_value(pre, x.dtype), None, nans
        # the neighbours' pass: how many keys lie at or under each rank's, and
        # the smallest above it.  One compare a rank serves both (`above` is
        # its complement), and ONE `reduce` of all the operands is one read of
        # the input by construction: a sum and a minimum as two reductions were
        # left two fusions by the compiler, two reads
        under = [key <= _offset(pre[r]) for r in range(k)]
        found = jax.lax.reduce(
            [u.astype(jnp.int32) for u in under] + [jnp.where(u, top, key) for u in under],
            [jnp.int32(0)] * k + [top] * k,
            lambda a, b: [p + q for p, q in zip(a[:k], b[:k])] + [jnp.minimum(p, q) for p, q in zip(a[k:], b[k:])],
            (axis,))
        upto = all_sum(jnp.stack(found[:k])).reshape(pre.shape)
        above = all_min(_offset(jnp.stack(found[k:]))).reshape(pre.shape)
        return _key_value(pre, x.dtype), _key_value(jnp.where(upto >= ranks + 2, pre, above), x.dtype), nans


def _select_by_group(cols, labels, k: int, lows, all_sum, all_min):
    """:func:`_select_ranks` group by group: ``cols`` is `kernels.pack_columns`'
    copy of a table (columns x R x lanes), ``labels`` (R x lanes, int32) name
    each value's group, any other number none (so a caller's padding).  A
    group is what a rank is without: its own pivots and its own counts, over
    its own values alone, and all ``k`` groups share every pass.  Returns the
    value of each group's rank, of the next rank (of no use where the group
    holds a NaN), 1 where it holds a NaN and 0 where none, and the group's
    size (NaNs included), each ``(k, columns, 1, 1)``.

    ``lows`` is one rank a group, which only the device knows: a DEVICE
    array of shape ``(k, columns, 1, 1)`` (or broadcastable to it), or a
    function that makes such ranks of the groups' sizes (``(k, columns)``,
    int32).  The rank of an empty group selects nothing a caller may use.

    The points are read through the two bodies of the kernel alone, on the
    packed copy: ``32 / _GROUP_BITS`` counting passes
    (``kernels.grouped_digit_counts``: each digit's count among the keys that
    agree with the group's settled bits, made the pivots' counts here) in a
    loop, ONE call of the kernel in the program, and the neighbours' pass
    (``kernels.grouped_neighbours``: the smallest key above each group's, or
    the evidence of a NaN in its place).  What the passes have counted is not
    read again: with no bit settled every member's key is in its group's
    range, so the first pass's total is the group's size, and the last pass's
    count at the chosen digit is the count at or under the group's key."""
    wide = cols.dtype == jnp.float64
    nbits = 64 if wide else 32
    shape = (k, cols.shape[0])
    rank_of = lows if callable(lows) else lambda sizes: jnp.broadcast_to(lows.astype(jnp.int32).reshape(k, -1), shape)

    def counting_pass(turn, settled):
        pre, below, _, sizes = settled  # the settled bits, the group's keys under their range, (of the last pass) at or under it
        shift = nbits - _GROUP_BITS * (turn + 1)
        held = all_sum(kernels.grouped_digit_counts(cols, labels, pre, shift, _GROUP_BITS, k))
        upto = below[:, None] + jnp.cumsum(held, axis=1, dtype=jnp.int32)  # at or under each digit's range
        under, total = upto[:, :-1], upto[:, -1]  # under each pivot; in the range and under it
        sizes = jnp.where(turn == 0, total, sizes)
        ranks = rank_of(sizes)[:, None]
        found = under <= ranks
        digit = jnp.sum(found, axis=1).astype(pre.dtype)
        return (pre | (digit << shift.astype(pre.dtype)),
                jnp.max(jnp.where(found, under, below[:, None]), axis=1),
                jnp.min(jnp.where(upto > ranks, upto, total[:, None]), axis=1),  # the first that passes the rank: the chosen digit's
                sizes)

    with jax.named_scope("quantile.count"):
        nothing = jnp.zeros(shape, jnp.int32)
        pre, _, upto, sizes = jax.lax.fori_loop(
            0, nbits // _GROUP_BITS, counting_pass, (jnp.zeros(shape, jnp.uint64 if wide else jnp.uint32), nothing, nothing, nothing))
        above = all_min(_offset(kernels.grouped_neighbours(cols, labels, _offset(pre), k)))
        nans = (above == 0).astype(jnp.int32)  # the smallest key there is, no number's: a NaN among the members made the minimum that
        high = jnp.where(upto >= rank_of(sizes) + 2, pre, above)  # the same key where ties reach that far
        return tuple(v[:, :, None, None] for v in (_key_value(pre, cols.dtype), _key_value(high, cols.dtype), nans, sizes))


def _select_passes(dtype, ranks: int, with_high: bool, grouped: bool = False) -> int:
    """How many passes over the input :func:`_select_ranks` makes, each of
    them one read of it (``tests/test_chip_compile.py`` counts them in the
    program compiled for the chip); ``grouped``: with ``ranks`` groups."""
    nbits = 64 if dtype == jnp.float64 else 32
    return -(-nbits // (_GROUP_BITS if grouped else _select_bits(ranks))) + (1 if with_high else 0)


def _interpolate(low, high, nans, plan: tuple, method: str, keepdims: bool, axis: int, scalar_q: bool):
    """``jnp.percentile``'s rule on the selected values: ``plan`` holds, a
    requested ``q``, the index of its rank among the selected ones, whether
    the next rank is its upper neighbour, and the upper weight."""
    wdt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    out = []
    for ix, has_high, hw in plan:
        lo = low[ix].astype(wdt)
        hi = high[ix].astype(wdt) if has_high and high is not None else lo
        if method == "linear":
            res = lo * wdt(1.0 - hw) + hi * wdt(hw)
        elif method == "lower":
            res = lo
        elif method == "higher":
            res = hi
        elif method == "nearest":
            res = lo if hw <= 0.5 else hi
        else:  # midpoint
            res = (lo + hi) * wdt(0.5)
        out.append(jnp.where(nans > 0, jnp.nan, res))
    res = jnp.stack(out)
    if not keepdims:
        res = jnp.squeeze(res, axis=axis + 1)
    return (res[0] if scalar_q else res).astype(low.dtype)


@functools.partial(jax.jit, static_argnames=("axis", "lows", "with_high", "plan", "method", "keepdims", "scalar_q", "n_true"))
def _select_program(x, *, axis, lows, with_high, plan, method, keepdims, scalar_q, n_true):
    """The whole selection as one program on one device, or on an array
    whose reduced axis no mesh divides."""
    valid = None
    if n_true != x.shape[axis]:
        valid = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis) < n_true
    low, high, nans = _select_ranks(x, axis, lows, with_high, valid, lambda a: a, lambda a: a)
    return _interpolate(low, high, nans, plan, method, keepdims, axis, scalar_q)


@functools.lru_cache(maxsize=64)
def _select_program_split(comm, axis, lows, with_high, plan, method, keepdims, scalar_q, n_true, padded):
    """The same over a mesh, along the split axis: each device counts in its
    own rows and the counts are all-reduced; nothing is gathered or sorted
    across devices."""
    spec = _P(*((None,) * axis), comm.axis_name)

    def body(block):
        valid = None
        if padded:
            first = jax.lax.axis_index(comm.axis_name) * block.shape[axis]
            valid = first + jax.lax.broadcasted_iota(jnp.int32, block.shape, axis) < n_true
        low, high, nans = _select_ranks(block, axis, lows, with_high, valid, comm.psum, comm.pmin)
        return _interpolate(low, high, nans, plan, method, keepdims, axis, scalar_q)

    return jax.jit(_shard_map(body, mesh=comm.mesh, in_specs=spec, out_specs=_P()))


def _percentile_selected(x: DNDarray, q, axis: int, interpolation: str, keepdims: bool) -> DNDarray:
    """Percentiles along ONE axis by exact selection (all ``q`` in one
    program, :func:`_select_ranks`), interpolated as ``jnp.percentile``
    interpolates.  The ranks and weights are worked out on the host in
    float64 from the static extent; the counts and pivots stay on the
    device, and nothing is read back."""
    if interpolation not in ("linear", "lower", "higher", "midpoint", "nearest"):
        raise ValueError(f"unknown interpolation {interpolation!r}")
    n = x.shape[axis]
    pos = np.atleast_1d(np.asarray(q, np.float64)) / 100.0 * (n - 1)
    low = np.clip(np.floor(pos), 0, n - 1).astype(np.int64)
    high = np.clip(np.ceil(pos), 0, n - 1).astype(np.int64)
    lows = tuple(sorted({int(v) for v in low}))
    plan = tuple((lows.index(int(l)), bool(h > l), float(p - np.floor(p))) for l, h, p in zip(low, high, pos))
    buf = x.larray_padded
    if not types.heat_type_is_inexact(x.dtype):
        buf = buf.astype(jnp.float32)
    # the upper neighbours cost one more pass, and only where a rank interpolates
    with_high = interpolation != "lower" and any(h for _, h, _ in plan)
    static = dict(axis=axis, lows=lows, with_high=with_high, plan=plan, method=interpolation, keepdims=keepdims,
                  scalar_q=np.ndim(q) == 0, n_true=n)
    with _span("statistics.quantiles", route="select", q=tuple(float(v) for v in np.atleast_1d(q)),
               passes=_select_passes(buf.dtype, len(lows), with_high), launches=1):
        dispatch.record_external_dispatch()
        if x.split == axis and x.comm.size > 1:
            result = _select_program_split(x.comm, *static.values(), x._pad > 0)(buf)
        else:
            result = _select_program(buf, **static)
            if x.split is not None and x.split != axis and x._pad:
                kept = x.split - (0 if keepdims or x.split < axis else 1) + (0 if static["scalar_q"] else 1)
                result = jax.lax.slice_in_dim(result, 0, x.shape[x.split], axis=kept)
    return DNDarray.from_dense(result, None, x.device, x.comm)


def median(x, axis=None, keepdims=False):
    """Median (statistics.py:1117): 50th percentile — for large 1-D split
    arrays this rides the PSRS sorted distribution, not a dense gather."""
    return percentile(x, 50.0, axis=axis, keepdims=keepdims)


def min(x, axis=None, out=None, keepdims=False):
    """Minimum along axes (statistics.py:1128)."""
    return _reduce_op(jnp.min, x, axis, neutral=_max_neutral(x), out=out, keepdims=keepdims)


def _min_neutral(x: DNDarray):
    dt = x.dtype
    if types.heat_type_is_exact(dt):
        if dt is types.bool:
            return False
        return types.iinfo(dt).min
    return -float("inf")


def _max_neutral(x: DNDarray):
    dt = x.dtype
    if types.heat_type_is_exact(dt):
        if dt is types.bool:
            return True
        return types.iinfo(dt).max
    return float("inf")


def minimum(x1, x2, out=None):
    """Element-wise minimum of two arrays (statistics.py:1279)."""
    return _binary_op(jnp.minimum, x1, x2, out)


def percentile(
    x,
    q,
    axis=None,
    out=None,
    interpolation: str = "linear",
    keepdims: bool = False,
    sketched: bool = False,
    sketch_size: Optional[int] = None,
):
    """q-th percentile (statistics.py:1443).

    Along ONE axis of at least ``_SELECT_MIN_EXTENT`` elements all ``q``
    are selected exactly in one program of counting passes over the
    order-preserving integer key (:func:`_select_ranks`: no sorted copy,
    O(columns) memory beside the input, over a mesh one all-reduce of
    counts a pass) and interpolated as ``jnp.percentile`` does; NaN, the
    zeros, infinities and ties give what it gives.  A shorter axis, several
    axes or a flattened n-D array take ``jnp.percentile`` itself (a sort),
    as before; a large 1-D split array rides the PSRS sorted distribution.
    The span ``statistics.quantiles`` says which (``route``).
    ``sketched=True`` estimates
    the percentile on a random subset of ``sketch_size`` samples along the
    reduction axis (statistics.py:1490-1532) — O(sketch_size log) instead
    of a full sort, with sampling error ~1/sqrt(sketch_size).
    """
    q_chk = np.asarray(q, dtype=np.float64)
    if not np.all((q_chk >= 0.0) & (q_chk <= 100.0)):  # NaN fails both too
        raise ValueError("Percentiles must be in the range [0, 100]")
    qa = jnp.asarray(q, dtype=jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)
    axis_s = sanitize_axis(x.shape, axis)
    if not sketched and out is None and x.ndim == 1 and axis_s in (None, 0):
        res = _percentile_sorted_1d(x, q, interpolation)
        if res is not None:
            if keepdims:
                res = res.reshape(res.shape + (1,)) if res.ndim else res.reshape((1,))
            return res
    select_axis = 0 if axis_s is None and x.ndim == 1 else axis_s
    if (
        not sketched
        and isinstance(select_axis, int)
        and x.shape[select_axis] >= _SELECT_MIN_EXTENT
        and not types.heat_type_is_complexfloating(x.dtype)
    ):
        # a long axis: exact selection by counting passes, no sorted copy
        return _to_out(_percentile_selected(x, q, select_axis, interpolation, keepdims), out)
    dense = x._dense()
    if not types.heat_type_is_inexact(x.dtype):
        dense = dense.astype(jnp.float32)
    if sketched:
        from . import random as ht_random

        # NB: min/max in this module are the DNDarray reductions
        n = dense.size if axis_s is None else dense.shape[axis_s]
        size = builtins.min(sketch_size or builtins.max(int(np.sqrt(n)) * 32, 1024), n)
        if size < n:
            idx = ht_random.randint(0, n, size=(size,), comm=x.comm)._dense()
            dense = dense.ravel()[idx] if axis_s is None else jnp.take(dense, idx, axis=axis_s)
    with _span("statistics.quantiles", route="sort", q=tuple(float(v) for v in np.atleast_1d(q_chk)),
               passes=1, launches=1):
        dispatch.record_external_dispatch()
        result = jnp.percentile(dense, qa, axis=axis_s, method=interpolation, keepdims=keepdims)
    res = DNDarray.from_dense(result, None, x.device, x.comm)
    return _to_out(res, out)


def skew(x, axis=None, unbiased: bool = True):
    """Skewness (3rd standardized moment; statistics.py:1729)."""
    m3 = _central_moment(x, 3, axis)
    v = var(x, axis, ddof=0)
    g1 = DNDarray.from_dense(m3._dense() / v._dense() ** 1.5, m3.split, m3.device, m3.comm)
    if unbiased:
        n = _axis_count(x, axis)
        g1_d = g1._dense() * np.sqrt(n * (n - 1)) / (n - 2)
        g1 = DNDarray.from_dense(g1_d, g1.split, g1.device, g1.comm)
    return g1


def std(x, axis=None, ddof: int = 0, keepdims: bool = False, **kwargs):
    """Standard deviation (statistics.py:1764)."""
    from . import exponential

    return exponential.sqrt(var(x, axis, ddof=ddof, keepdims=keepdims, **kwargs))


# ----------------------------------------------------------------------
# several reductions of one array in ONE read of it (PERF.md, PR 36)
# ----------------------------------------------------------------------
#: Rows the moments' shift is the median of, spread evenly over the reduced
#: extent, and how many standard deviations it may lie off the mean before
#: the sums are taken again about the mean itself (:func:`_moments_fn`).
_SHIFT_ROWS = 8
_SHIFT_FAR = 4.0


def _whole(leaf):
    return leaf


def _masked(a, pad, neutral):
    """``a`` with the canonical padding of the reduced split axis (``pad``:
    ``(axis, true extent)``, or None) overwritten by ``neutral``."""
    return a if pad is None else dispatch._mask_pad(a, split=pad[0], extent=pad[1], neutral=neutral)


def _extrema_fn(value, *, axes, keepdims, pad, neutrals):
    """Minimum and maximum over ``axes``: two reductions of one operand, which
    the compiler makes one fusion and one read."""
    a = value(_whole)
    return (jnp.min(_masked(a, pad, neutrals[0]), axis=axes, keepdims=keepdims),
            jnp.max(_masked(a, pad, neutrals[1]), axis=axes, keepdims=keepdims))


def _moments_fn(value, *, axes, keepdims, pad, ddof):
    """Mean and variance over ``axes`` from ONE read of the input: the sums of
    ``x - shift`` and of its square (two reductions of one operand: one
    fusion), ``mean = shift + S1 / n``, ``var = (S2 - S1^2 / n) / (n - ddof)``,
    in float32 at least.

    The shift is the median of ``_SHIFT_ROWS`` elements spread evenly over the
    longest reduced axis (an element of the data, so a constant column's
    variance is exactly 0), taken of the LEAVES' rows and not of the chain's
    value.  What it costs to be ``d`` standard deviations off the mean is a
    relative error of ``(1 + d^2)`` times the sums' own rounding, and a median
    lies within one standard deviation of the mean.  The sample's median
    need not: where it turns out more than ``_SHIFT_FAR`` off in some column
    (known from the sums themselves), the sums are taken once more about the
    first pass's mean, so the result is never worse than the two-pass one and
    the second read is paid only by such an input (rows sorted by a heavy
    tail, the sampled rows all outliers)."""
    a = value(_whole)
    wide = jnp.promote_types(a.dtype, jnp.float32) if jnp.issubdtype(a.dtype, jnp.inexact) else jnp.dtype(jnp.float32)
    kept = tuple(1 if d in axes else s for d, s in enumerate(a.shape))
    extent = {d: pad[1] if pad is not None and d == pad[0] else a.shape[d] for d in axes}
    n = int(np.prod([extent[d] for d in axes], dtype=np.int64))
    if not axes:  # nothing is reduced: every element is its own mean
        mean, m2 = a.astype(wide), jnp.zeros(kept, wide)
    elif n == 0:  # nothing to reduce: no mean
        mean = m2 = jnp.full(kept, jnp.nan, wide)
    else:
        long = builtins.max(axes, key=extent.get)
        rows = {d: [i * (extent[d] // _SHIFT_ROWS) for i in range(_SHIFT_ROWS)] if d == long and extent[d] >= _SHIFT_ROWS
                else [0] for d in axes}

        def sampled(leaf):
            # the rows of a leaf that spans a reduced axis; one that is broadcast along it as it is
            for d, at in rows.items():
                own = d - (a.ndim - np.ndim(leaf))
                if own >= 0 and leaf.shape[own] == a.shape[d]:
                    leaf = jnp.concatenate([jax.lax.slice_in_dim(leaf, i, i + 1, axis=own) for i in at], axis=own)
            return leaf

        def sums(about):
            d = _masked(value(_whole).astype(wide) - about, pad, 0)
            s1, s2 = (jnp.sum(v, axis=axes, keepdims=True) for v in (d, d * d))
            off = s1 / n
            return about + off, s2 - s1 * off, off

        sample = jnp.sort(value(sampled).astype(wide), axis=long)  # NaNs last
        shift = jax.lax.slice_in_dim(sample, len(rows[long]) // 2, len(rows[long]) // 2 + 1, axis=long)
        mean, m2, off = sums(jnp.where(jnp.isfinite(shift), shift, 0))
        far = jnp.any(off * off * n > _SHIFT_FAR ** 2 * jnp.abs(m2))  # false for a NaN, which stays one
        # a loop of at most one turn and not `lax.cond`: a branch of a conditional takes the table
        # row-major on the chip (16 GiB at 2^25 x 50, where the long axis is laid out minor), a loop as it lies
        _, mean, m2 = jax.lax.while_loop(lambda s: s[0], lambda s: (jnp.zeros((), bool),) + sums(s[1])[:2], (far, mean, m2))
    var = jnp.maximum(m2, 0) / builtins.max(n - ddof, 0)
    out = a.dtype if jnp.issubdtype(a.dtype, jnp.inexact) else wide
    return tuple((v if keepdims else jnp.squeeze(v, axis=axes)).astype(out) for v in (mean, var))


def _reduce_together(fn, x: DNDarray, axis, keepdims: bool, **kwargs) -> Tuple[DNDarray, ...]:
    """``fn``'s results (several reductions of ``x`` over the same axes) from
    ONE program that reads ``x`` once, through its pending chain where it has
    one (``dispatch.chain_apply``, as :func:`__reduce_op`): a waiting in-place
    store stays waiting.  The canonical padding of a reduced split axis is
    masked inside ``fn`` (``pad``), each result in its own neutral."""
    axis = sanitize_axis(x.shape, axis)
    axes = tuple(range(x.ndim)) if axis is None else axis if isinstance(axis, tuple) else (axis,)
    split_reduced = x.split is not None and x.split in axes
    kwargs.update(axes=axes, keepdims=bool(keepdims),
                  pad=(x.split, x.shape[x.split]) if split_reduced and x._pad > 0 else None)
    if x._planar is None and not types.heat_type_is_complexfloating(x.dtype):
        results = dispatch.chain_apply(fn, x._fusion_source, kwargs, lazy=True)
    else:
        buf = x.larray_padded
        results = fn(lambda take: take(buf), **kwargs)
    return tuple(_wrap_reduced(r, x, axes, bool(keepdims)) for r in results)


def min_max(x: DNDarray, axis=None, keepdims: bool = False) -> Tuple[DNDarray, DNDarray]:
    """``(min(x, axis), max(x, axis))`` from one read of ``x``."""
    return _reduce_together(_extrema_fn, x, axis, keepdims, neutrals=(_max_neutral(x), _min_neutral(x)))


def mean_var(x: DNDarray, axis=None, ddof: int = 0, keepdims: bool = False) -> Tuple[DNDarray, DNDarray]:
    """``(mean(x, axis), var(x, axis, ddof))`` of a real array from one read
    of ``x`` (:func:`_moments_fn`); integers are taken as float32."""
    return _reduce_together(_moments_fn, x, axis, keepdims, ddof=ddof)


def var(x, axis=None, ddof: int = 0, keepdims: bool = False, **kwargs):
    """Variance (statistics.py:1903).

    One read of ``x``: shifted sums about the median of a few of its rows,
    taken again about the mean where that shift proves far off
    (:func:`_moments_fn`, which says what bounds the error).  The reference's
    pairwise merge of the processes' moments (``__merge_moments``) is
    unnecessary because the global reduction already sees all shards.
    """
    if kwargs:
        raise TypeError(f"var() got unexpected keyword arguments {sorted(kwargs)}")
    if not isinstance(ddof, int):
        raise ValueError(f"ddof must be integer, is {type(ddof)}")
    if ddof < 0:
        raise ValueError(f"Expected ddof >= 0, got {ddof}")
    if x._planar is None and not types.heat_type_is_complexfloating(x.dtype):
        return mean_var(x, axis, ddof, keepdims)[1]
    # a complex array: the dense two-pass form, as `__reduce_op` keeps such arrays off the chain
    return _dense_reduce(lambda a, axis, keepdims: jnp.var(a, axis=axis, ddof=ddof, keepdims=keepdims), x, axis, keepdims)
