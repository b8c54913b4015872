"""Complex-number operations, analog of heat/core/complex_math.py.

Planar-backed complex arrays (``DNDarray._planar``, produced by the
planar FFT engine) get plane-level fast paths: the result is computed
from the (re, im) planes instead of composing a complex array first."""

from __future__ import annotations

import jax.numpy as jnp

from ._operations import __local_op as _local_op
from .dndarray import DNDarray

__all__ = ["angle", "conj", "conjugate", "imag", "real", "real_if_close"]


def _plane_result(x: DNDarray, plane) -> DNDarray:
    """Wrap one real plane (already padded, canonically placed)."""
    from . import types

    return DNDarray(
        plane, x.shape, types.canonical_heat_type(plane.dtype), x.split, x.device, x.comm
    )


def angle(x, deg: bool = False, out=None):
    """Argument of complex values (complex_math.py:15)."""
    if isinstance(x, DNDarray) and x._planar is not None and out is None:
        re, im = x._planar
        a = jnp.arctan2(im, re)
        return _plane_result(x, jnp.rad2deg(a) if deg else a)
    return _local_op(lambda a: jnp.angle(a, deg=deg), x, out, no_cast=True)


def conjugate(x, out=None):
    """Complex conjugate (complex_math.py:48)."""
    if isinstance(x, DNDarray) and x._planar is not None and out is None:
        re, im = x._planar
        return DNDarray.from_planar(re, -im, x.shape, x.split, x.device, x.comm)
    return _local_op(jnp.conjugate, x, out, no_cast=True)


conj = conjugate


def imag(x, out=None):
    """Imaginary part (complex_math.py:78)."""
    if isinstance(x, DNDarray) and x._planar is not None and out is None:
        return _plane_result(x, x._planar[1])
    return _local_op(jnp.imag, x, out, no_cast=True)


def real(x, out=None):
    """Real part (complex_math.py:98)."""
    if isinstance(x, DNDarray) and x._planar is not None and out is None:
        return _plane_result(x, x._planar[0])
    return _local_op(jnp.real, x, out, no_cast=True)


def real_if_close(x, tol: float = 100.0):
    """Return the real part when all imaginary components are within
    ``tol`` machine epsilons of zero (numpy extension beyond the
    reference's checklist).  The all-close check is a global reduction."""
    if not isinstance(x, DNDarray):
        raise TypeError(f"expected x to be a DNDarray, but was {type(x)}")
    from . import types

    if not types.heat_type_is_complexfloating(x.dtype):
        return x
    import numpy as np

    if tol > 1:  # numpy semantics: tol > 1 scales machine eps, else absolute
        tol = tol * float(np.finfo(x._dense().real.dtype).eps)
    if bool(jnp.all(jnp.abs(jnp.imag(x._dense())) < tol)):
        return real(x)
    return x
