"""Arithmetic operations, analog of heat/core/arithmetics.py (39 exports).

Every function is a thin shim over the generic wrappers in
core/_operations.py; the distributed behavior documented in the reference
(split matching, Allreduce on reduced split axes, Exscan for cumops) falls
out of the sharded-jnp execution model.
"""

from __future__ import annotations

import jax.numpy as jnp

from . import types
from ._operations import __binary_op as _binary_op
from ._operations import __cum_op as _cum_op
from ._operations import __local_op as _local_op
from ._operations import __reduce_op as _reduce_op
from .dndarray import DNDarray

__all__ = [
    "add",
    "bitwise_and",
    "bitwise_not",
    "bitwise_or",
    "bitwise_xor",
    "copysign",
    "cumprod",
    "cumproduct",
    "cumsum",
    "diff",
    "div",
    "divide",
    "divmod",
    "floordiv",
    "floor_divide",
    "fmod",
    "gcd",
    "hypot",
    "invert",
    "lcm",
    "left_shift",
    "mod",
    "mul",
    "multiply",
    "nan_to_num",
    "nanprod",
    "nansum",
    "neg",
    "negative",
    "pos",
    "positive",
    "pow",
    "power",
    "prod",
    "remainder",
    "right_shift",
    "sub",
    "subtract",
    "sum",
]


def add(t1, t2, out=None, where=True):
    """Element-wise addition (arithmetics.py:42)."""
    return _binary_op(jnp.add, t1, t2, out, where)


def _check_int_or_bool(t1, t2, name):
    for t in (t1, t2):
        if isinstance(t, DNDarray) and not types.heat_type_is_exact(t.dtype):
            raise TypeError(f"{name} is only supported for integer or boolean types, got {t.dtype.__name__}")
        if isinstance(t, float):
            raise TypeError(f"{name} is only supported for integer or boolean types, got float")


def bitwise_and(t1, t2, out=None, where=True):
    """Element-wise AND of bits (arithmetics.py:175)."""
    _check_int_or_bool(t1, t2, "bitwise_and")
    return _binary_op(jnp.bitwise_and, t1, t2, out, where)


def bitwise_or(t1, t2, out=None, where=True):
    """Element-wise OR of bits (arithmetics.py:252)."""
    _check_int_or_bool(t1, t2, "bitwise_or")
    return _binary_op(jnp.bitwise_or, t1, t2, out, where)


def bitwise_xor(t1, t2, out=None, where=True):
    """Element-wise XOR of bits (arithmetics.py:329)."""
    _check_int_or_bool(t1, t2, "bitwise_xor")
    return _binary_op(jnp.bitwise_xor, t1, t2, out, where)


def bitwise_not(t, out=None):
    """Element-wise bit inversion, alias invert (arithmetics.py:1369)."""
    return invert(t, out)


def copysign(t1, t2, out=None, where=True):
    """Magnitude of t1 with sign of t2 (arithmetics.py:406)."""
    return _binary_op(jnp.copysign, t1, t2, out, where)


def cumprod(t, axis, dtype=None, out=None):
    """Cumulative product along ``axis`` (arithmetics.py:468)."""
    return _cum_op(jnp.cumprod, t, axis, neutral=1, out=out, dtype=dtype)


cumproduct = cumprod


def cumsum(t, axis, dtype=None, out=None):
    """Cumulative sum along ``axis`` (arithmetics.py:526)."""
    return _cum_op(jnp.cumsum, t, axis, neutral=0, out=out, dtype=dtype)


def diff(a, n: int = 1, axis: int = -1, prepend=None, append=None):
    """n-th discrete difference along an axis (arithmetics.py:584)."""
    if n < 0:
        raise ValueError(f"diff requires that n be a positive number, got {n}")
    if not isinstance(a, DNDarray):
        raise TypeError(f"'a' must be a DNDarray, got {type(a)}")
    if n == 0:
        return a
    from .stride_tricks import sanitize_axis

    axis = sanitize_axis(a.shape, axis)
    dense = a._dense()
    pre = prepend._dense() if isinstance(prepend, DNDarray) else prepend
    app = append._dense() if isinstance(append, DNDarray) else append
    kwargs = {}
    if pre is not None:
        kwargs["prepend"] = jnp.asarray(pre)
    if app is not None:
        kwargs["append"] = jnp.asarray(app)
    result = jnp.diff(dense, n=n, axis=axis, **kwargs)
    split = a.split if a.split is None or a.split < result.ndim else None
    return DNDarray.from_dense(result, split, a.device, a.comm)


def div(t1, t2, out=None, where=True):
    """Element-wise true division (arithmetics.py:717)."""
    return _binary_op(jnp.true_divide, t1, t2, out, where)


divide = div


def divmod(t1, t2, out1=None, out2=None, out=None, where=True):
    """Simultaneous floordiv and mod (arithmetics.py:794)."""
    if out is None:
        out = (out1, out2)
    if not isinstance(out, tuple) or len(out) != 2:
        raise ValueError("out must be a 2-tuple")
    d = floordiv(t1, t2, out[0], where)
    m = mod(t1, t2, out[1], where)
    return d, m


def floordiv(t1, t2, out=None, where=True):
    """Element-wise floor division (arithmetics.py:879)."""
    return _binary_op(jnp.floor_divide, t1, t2, out, where)


floor_divide = floordiv


def fmod(t1, t2, out=None, where=True):
    """C-style remainder (sign of dividend) (arithmetics.py:956)."""
    return _binary_op(jnp.fmod, t1, t2, out, where)


def gcd(t1, t2, out=None, where=True):
    """Greatest common divisor (arithmetics.py:1032)."""
    _check_int_or_bool(t1, t2, "gcd")
    return _binary_op(jnp.gcd, t1, t2, out, where)


def hypot(t1, t2, out=None, where=True):
    """sqrt(t1^2 + t2^2) (arithmetics.py:1102)."""
    for t in (t1, t2):
        if isinstance(t, DNDarray) and types.heat_type_is_exact(t.dtype) or isinstance(t, int):
            raise TypeError("hypot is only supported for floating point types")
    return _binary_op(jnp.hypot, t1, t2, out, where)


def invert(t, out=None):
    """Element-wise bitwise NOT (arithmetics.py:1369)."""
    if isinstance(t, DNDarray) and not types.heat_type_is_exact(t.dtype):
        raise TypeError(f"invert is only supported for integer or boolean types, got {t.dtype.__name__}")
    return _local_op(jnp.invert, t, out, no_cast=True)


def lcm(t1, t2, out=None, where=True):
    """Least common multiple (arithmetics.py:1444)."""
    _check_int_or_bool(t1, t2, "lcm")
    return _binary_op(jnp.lcm, t1, t2, out, where)


def left_shift(t1, t2, out=None, where=True):
    """Shift bits left (arithmetics.py:1512)."""
    _check_int_or_bool(t1, t2, "left_shift")
    return _binary_op(jnp.left_shift, t1, t2, out, where)


def mod(t1, t2, out=None, where=True):
    """Python-style modulo (sign of divisor), alias remainder
    (arithmetics.py:1582)."""
    return _binary_op(jnp.mod, t1, t2, out, where)


remainder = mod


def mul(t1, t2, out=None, where=True):
    """Element-wise multiplication (arithmetics.py:1660)."""
    return _binary_op(jnp.multiply, t1, t2, out, where)


multiply = mul


def nan_to_num(t, nan: float = 0.0, posinf=None, neginf=None, out=None):
    """Replace NaN/Inf with finite numbers (arithmetics.py:1738)."""
    return _local_op(jnp.nan_to_num, t, out, no_cast=True, nan=nan, posinf=posinf, neginf=neginf)


def nanprod(a, axis=None, out=None, keepdims=False):
    """Product treating NaN as 1 (arithmetics.py:1791)."""
    return _reduce_op(jnp.nanprod, a, axis, neutral=1, out=out, keepdims=keepdims)


def nansum(a, axis=None, out=None, keepdims=False):
    """Sum treating NaN as 0 (arithmetics.py:1836)."""
    return _reduce_op(jnp.nansum, a, axis, neutral=0, out=out, keepdims=keepdims)


def neg(a, out=None):
    """Element-wise negation (arithmetics.py:1880)."""
    return _local_op(jnp.negative, a, out, no_cast=True)


negative = neg


def pos(a, out=None):
    """Element-wise +a (copy) (arithmetics.py:1928)."""
    return _local_op(jnp.positive, a, out, no_cast=True)


positive = pos


def pow(t1, t2, out=None, where=True):
    """Element-wise power (arithmetics.py:1976)."""
    return _binary_op(jnp.power, t1, t2, out, where)


power = pow


def prod(a, axis=None, out=None, keepdims=False):
    """Product of elements over axes (arithmetics.py:2054)."""
    return _reduce_op(jnp.prod, a, axis, neutral=1, out=out, keepdims=keepdims)


def right_shift(t1, t2, out=None, where=True):
    """Shift bits right (arithmetics.py:2100)."""
    _check_int_or_bool(t1, t2, "right_shift")
    return _binary_op(jnp.right_shift, t1, t2, out, where)


def sub(t1, t2, out=None, where=True):
    """Element-wise subtraction (arithmetics.py:2170)."""
    return _binary_op(jnp.subtract, t1, t2, out, where)


subtract = sub


def sum(a, axis=None, out=None, keepdims=False):
    """Sum of elements over axes (arithmetics.py:2248)."""
    return _reduce_op(jnp.sum, a, axis, neutral=0, out=out, keepdims=keepdims)


# ----------------------------------------------------------------------
# in-place variants (reference: `_`-suffixed functions bound as DNDarray
# methods and `__i*__` dunders, e.g. add_ arithmetics.py:135,195-196).
# Functional substrate underneath: compute out-of-place, then swap the
# backing array with a cast-safety check (dndarray._iop).  Under the
# dispatch layer the out-of-place result is a PENDING chain, so `a += b`
# compiles as one cached executable whose output aliases a's donated
# backing buffer when it is provably unshared (core/dispatch.cast_store);
# where the chain reads no other buffer of a's size (`a *= 2`) it waits,
# with whatever follows it, for a's first reader (dispatch.defer_store).
# ----------------------------------------------------------------------
from .dndarray import _iop as __iop  # noqa: E402


def _inplace(t1, result) -> DNDarray:
    if not isinstance(t1, DNDarray):
        raise TypeError(f"in-place operations require a DNDarray target, got {type(t1)}")
    return __iop(t1, result)


def add_(t1, t2):
    """In-place element-wise addition (arithmetics.py:135)."""
    return _inplace(t1, add(t1, t2))


def bitwise_and_(t1, t2):
    """In-place bitwise AND (arithmetics.py:265)."""
    return _inplace(t1, bitwise_and(t1, t2))


def bitwise_or_(t1, t2):
    """In-place bitwise OR (arithmetics.py:415)."""
    return _inplace(t1, bitwise_or(t1, t2))


def bitwise_xor_(t1, t2):
    """In-place bitwise XOR (arithmetics.py:556)."""
    return _inplace(t1, bitwise_xor(t1, t2))


def copysign_(t1, t2):
    """In-place copysign (arithmetics.py:676)."""
    return _inplace(t1, copysign(t1, t2))


def cumprod_(t, axis):
    """In-place cumulative product (arithmetics.py:~800)."""
    return _inplace(t, cumprod(t, axis))


cumproduct_ = cumprod_


def cumsum_(t, axis):
    """In-place cumulative sum (arithmetics.py:~870)."""
    return _inplace(t, cumsum(t, axis))


def div_(t1, t2):
    """In-place true division (arithmetics.py:~1100)."""
    return _inplace(t1, div(t1, t2))


divide_ = div_


def floordiv_(t1, t2):
    """In-place floor division (arithmetics.py:~1330)."""
    return _inplace(t1, floordiv(t1, t2))


floor_divide_ = floordiv_


def fmod_(t1, t2):
    """In-place C-style remainder (arithmetics.py:~1000)."""
    return _inplace(t1, fmod(t1, t2))


def gcd_(t1, t2):
    """In-place greatest common divisor (arithmetics.py:~1070)."""
    return _inplace(t1, gcd(t1, t2))


def hypot_(t1, t2):
    """In-place hypot (arithmetics.py:~1140)."""
    return _inplace(t1, hypot(t1, t2))


def invert_(t):
    """In-place bitwise NOT (arithmetics.py:~1410)."""
    return _inplace(t, invert(t))


bitwise_not_ = invert_


def lcm_(t1, t2):
    """In-place least common multiple (arithmetics.py:~1480)."""
    return _inplace(t1, lcm(t1, t2))


def left_shift_(t1, t2):
    """In-place left shift (arithmetics.py:~1550)."""
    return _inplace(t1, left_shift(t1, t2))


def mod_(t1, t2):
    """In-place modulo (arithmetics.py:~1620)."""
    return _inplace(t1, mod(t1, t2))


remainder_ = mod_


def mul_(t1, t2):
    """In-place multiplication (arithmetics.py:~1700)."""
    return _inplace(t1, mul(t1, t2))


multiply_ = mul_


def nan_to_num_(t, nan: float = 0.0, posinf=None, neginf=None):
    """In-place NaN/Inf replacement (arithmetics.py:~1780)."""
    return _inplace(t, nan_to_num(t, nan, posinf, neginf))


def neg_(t):
    """In-place negation (arithmetics.py:~1900)."""
    return _inplace(t, neg(t))


negative_ = neg_


def pos_(t):
    """In-place +t (arithmetics.py:~1950)."""
    return _inplace(t, pos(t))


positive_ = pos_


def pow_(t1, t2):
    """In-place power (arithmetics.py:~2010)."""
    return _inplace(t1, pow(t1, t2))


power_ = pow_


def right_shift_(t1, t2):
    """In-place right shift (arithmetics.py:~2140)."""
    return _inplace(t1, right_shift(t1, t2))


def sub_(t1, t2):
    """In-place subtraction (arithmetics.py:~2210)."""
    return _inplace(t1, sub(t1, t2))


subtract_ = sub_


# method + dunder bindings, mirroring the reference's module-bottom
# assignments (arithmetics.py:195-196 etc.)
for _name in (
    "add_", "bitwise_and_", "bitwise_not_", "bitwise_or_", "bitwise_xor_",
    "copysign_", "cumprod_", "cumproduct_", "cumsum_", "div_", "divide_",
    "floordiv_", "floor_divide_", "fmod_", "gcd_", "hypot_", "invert_",
    "lcm_", "left_shift_", "mod_", "mul_", "multiply_", "nan_to_num_",
    "neg_", "negative_", "pos_", "positive_", "pow_", "power_",
    "remainder_", "right_shift_", "sub_", "subtract_",
):
    setattr(DNDarray, _name, globals()[_name])
DNDarray.__ilshift__ = left_shift_
DNDarray.__irshift__ = right_shift_
DNDarray.__iand__ = bitwise_and_
DNDarray.__ior__ = bitwise_or_
DNDarray.__ixor__ = bitwise_xor_

__all__ += [
    "add_", "bitwise_and_", "bitwise_not_", "bitwise_or_", "bitwise_xor_",
    "copysign_", "cumprod_", "cumproduct_", "cumsum_", "div_", "divide_",
    "floordiv_", "floor_divide_", "fmod_", "gcd_", "hypot_", "invert_",
    "lcm_", "left_shift_", "mod_", "mul_", "multiply_", "nan_to_num_",
    "neg_", "negative_", "pos_", "positive_", "pow_", "power_",
    "remainder_", "right_shift_", "sub_", "subtract_",
]


# ---- numpy extensions beyond the reference's checklist -------------------

true_divide = div


def float_power(t1, t2, out=None, where=True):
    """t1**t2 computed in at least float64 precision (numpy extension)."""
    return _binary_op(jnp.float_power, t1, t2, out, where)


def heaviside(t1, t2, out=None, where=True):
    """Heaviside step function with value t2 at 0 (numpy extension)."""
    return _binary_op(jnp.heaviside, t1, t2, out, where)


def _nancumsum_op(a, axis):
    return jnp.nancumsum(a, axis=axis)


def _nancumprod_op(a, axis):
    return jnp.nancumprod(a, axis=axis)


def nancumsum(t, axis, dtype=None, out=None):
    """Cumulative sum treating NaN as zero (numpy extension).

    Module-level op callable (not a per-call lambda): the dispatch-layer
    executable cache keys on the callable's identity, and a fresh lambda
    per call would miss forever."""
    return _cum_op(_nancumsum_op, t, axis, 0, out, dtype)


def nancumprod(t, axis, dtype=None, out=None):
    """Cumulative product treating NaN as one (numpy extension)."""
    return _cum_op(_nancumprod_op, t, axis, 1, out, dtype)


def ediff1d(ary, to_end=None, to_begin=None):
    """Differences of the flattened array, with optional end caps (numpy
    extension).  1-D result; distributed along axis 0 when the input is
    split."""
    if not isinstance(ary, DNDarray):
        raise TypeError(f"expected ary to be a DNDarray, but was {type(ary)}")
    te = to_end._dense() if isinstance(to_end, DNDarray) else to_end
    tb = to_begin._dense() if isinstance(to_begin, DNDarray) else to_begin
    res = jnp.ediff1d(ary._dense().ravel(), to_end=te, to_begin=tb)
    return DNDarray.from_dense(res, 0 if ary.split is not None else None, ary.device, ary.comm)


def gradient(f, *varargs, axis=None, edge_order: int = 1):
    """Second-order central differences (numpy extension).

    Supports scalar spacing per axis (``varargs``); returns one DNDarray
    per requested axis (a single DNDarray for a single axis).
    """
    if not isinstance(f, DNDarray):
        raise TypeError(f"expected f to be a DNDarray, but was {type(f)}")
    if edge_order != 1:
        raise NotImplementedError("gradient: only edge_order=1 is supported")
    spacing = [v._dense() if isinstance(v, DNDarray) else v for v in varargs]
    res = jnp.gradient(f._dense(), *spacing, axis=axis)
    single = not isinstance(res, (list, tuple))
    outs = [DNDarray.from_dense(r, f.split, f.device, f.comm) for r in ([res] if single else res)]
    return outs[0] if single else outs


def trapz(y, x=None, dx: float = 1.0, axis: int = -1):
    """Trapezoidal-rule integral along an axis (numpy extension)."""
    if not isinstance(y, DNDarray):
        raise TypeError(f"expected y to be a DNDarray, but was {type(y)}")
    xs = x._dense() if isinstance(x, DNDarray) else x
    trapezoid = getattr(jnp, "trapezoid", None) or jnp.trapz
    res = trapezoid(y._dense(), x=xs, dx=dx, axis=axis)
    ax = axis % y.ndim
    if y.split is None or y.split == ax:
        out_split = None
    else:
        out_split = y.split - (1 if ax < y.split else 0)
    return DNDarray.from_dense(res, out_split, y.device, y.comm)


trapezoid = trapz


def interp(x, xp, fp, left=None, right=None, period=None):
    """1-D linear interpolation of x into sample points (xp, fp) (numpy
    extension).  The sample table is replicated; the query array keeps its
    distribution."""
    if not isinstance(x, DNDarray):
        raise TypeError(f"expected x to be a DNDarray, but was {type(x)}")
    xpd = xp._dense() if isinstance(xp, DNDarray) else jnp.asarray(xp)
    fpd = fp._dense() if isinstance(fp, DNDarray) else jnp.asarray(fp)
    res = jnp.interp(x._dense(), xpd, fpd, left=left, right=right, period=period)
    return DNDarray.from_dense(res, x.split, x.device, x.comm)


__all__ += [
    "ediff1d", "float_power", "gradient", "heaviside", "interp",
    "nancumprod", "nancumsum", "trapezoid", "trapz", "true_divide",
]
