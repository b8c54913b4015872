"""Generic operation wrappers, analog of heat/core/_operations.py.

The reference funnels nearly the whole NumPy API through four generic
wrappers: ``__binary_op`` (_operations.py:22), ``__cum_op`` (:230),
``__local_op`` (:331) and ``__reduce_op`` (:404), each mixing local torch
calls with explicit MPI collectives.  Here the same four wrappers exist but
the "communication half" vanishes: operands are global sharded jax.Arrays,
so a single jnp call *is* the distributed op — XLA/GSPMD emits any psum /
all-gather / resharding.  What remains of the distribution logic is the
pad-and-mask bookkeeping (see core/dndarray.py docstring):

* element-wise ops run straight on the padded buffers (padding is garbage
  in, garbage out — never observed);
* reductions/scans that cross the split axis first overwrite padding with
  the op's neutral element (the analog of the reference's neutral-element
  fill for empty local chunks, _operations.py:450-459).
"""

from __future__ import annotations

import builtins
from typing import Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.comm import sanitize_comm
from . import dispatch
from . import types
from .devices import sanitize_device
from .dndarray import DNDarray
from .sanitation import sanitize_out, store_out
from .stride_tricks import broadcast_shape, sanitize_axis

__all__ = []

Scalar = Union[int, float, bool, complex]


def _as_dndarray(x, reference: Optional[DNDarray] = None) -> DNDarray:
    from . import factories

    if isinstance(x, DNDarray):
        return x
    device = reference.device if reference is not None else None
    comm = reference.comm if reference is not None else None
    return factories.array(x, device=device, comm=comm)


def _out_split_binary(t1: DNDarray, t2: DNDarray, out_shape) -> Optional[int]:
    """Output split of a broadcasting binary op: splits are right-aligned
    into the output shape; the first operand's split wins (matching the
    dominant-operand choice in _operations.py:173-194)."""
    nd_out = len(out_shape)
    for t in (t1, t2):
        if t.split is not None:
            cand = t.split + (nd_out - t.ndim)
            # a broadcast (size-1) split dim cannot carry the distribution
            if t.shape[t.split] == out_shape[cand] and out_shape[cand] != 1:
                return cand
    return None


# ----------------------------------------------------------------------
# planar (re, im) fast paths — keep complex chains like fftn(x)*H ->
# ifftn on the planes instead of composing a complex array between
# every op (VERDICT r3 #7).  The full plane-preservation inventory
# lives in docs/planar_ops.md.
# ----------------------------------------------------------------------
def _planar_rule(operation) -> Optional[str]:
    if operation is jnp.add or operation is jnp.subtract:
        return "addsub"
    if operation is jnp.multiply:
        return "mul"
    if operation is jnp.true_divide:
        return "div"
    return None


def _planar_pair(t, ref: DNDarray):
    """(re, im|None) of an operand against the planar reference — padded
    planes for arrays (same layout required), python reals for scalars.
    None -> this operand cannot ride the plane path."""
    if isinstance(t, DNDarray):
        if t._planar is not None:
            if t.shape != ref.shape or t.split != ref.split:
                return None
            return t._planar
        if types.heat_type_is_complexfloating(t.dtype):
            return None  # non-planar complex storage: host-backed anyway
        if t.shape != ref.shape or t.split != ref.split:
            return None
        return (t.larray_padded, None)
    if isinstance(t, (int, float, complex, np.number)) or (
        isinstance(t, (np.ndarray, jax.Array)) and t.ndim == 0
    ):
        c = complex(t)
        return (c.real, c.imag if c.imag != 0.0 else None)
    return None


def _try_planar_binary(operation, t1, t2) -> Optional[DNDarray]:
    rule = _planar_rule(operation)
    if rule is None:
        return None
    ref = None
    for t in (t1, t2):
        if isinstance(t, DNDarray) and t._planar is not None:
            ref = t
            break
    if ref is None:
        return None
    a = _planar_pair(t1, ref)
    b = _planar_pair(t2, ref)
    if a is None or b is None:
        return None
    ra, ia = a
    rb, ib = b
    if rule == "addsub":
        rr = operation(ra, rb)
        if ia is None:
            ii = operation(jnp.zeros((), jnp.result_type(ra)), ib)
        elif ib is None:
            ii = ia
        else:
            ii = operation(ia, ib)
    elif rule == "mul":
        if ib is None:
            rr, ii = ra * rb, ia * rb
        elif ia is None:
            rr, ii = ra * rb, ra * ib
        else:
            rr = ra * rb - ia * ib
            ii = ra * ib + ia * rb
    else:  # div
        if ib is None:  # (ra + i ia) / rb
            rr, ii = ra / rb, (0.0 if ia is None else ia) / rb
        else:
            den = rb * rb + ib * ib
            ia_ = ia if ia is not None else 0.0
            rr = (ra * rb + ia_ * ib) / den
            ii = (ia_ * rb - ra * ib) / den
    rr = jnp.asarray(rr)
    ii = jnp.broadcast_to(jnp.asarray(ii, rr.dtype), rr.shape)
    if rr.shape != ref._padded_shape:
        return None  # scalar-only combination degenerated; let the slow path run
    return DNDarray.from_planar(rr, ii, ref.shape, ref.split, ref.device, ref.comm)


#: python-number operand types eligible for the cached-leaf fast track.
#: np scalars keep the generic factories conversion (their dtype handling
#: — x64 demotion, unsigned kinds — lives there); complex scalars too:
#: under x64 factories picks complex128 while the leaf would be
#: complex64, which could flip precision-sensitive comparisons.
_PY_NUMBERS = (builtins.int, builtins.float, builtins.bool)


def _try_scalar_fast(operation, t1, t2, fn_kwargs) -> Optional[DNDarray]:
    """Array (op) python-scalar without the factories round trip: the
    scalar becomes a cached 0-d leaf (same canonical dtype the generic
    conversion would produce, so promotion is identical) and the op joins
    the carrier's pending chain.  None -> take the generic path."""
    if isinstance(t1, DNDarray) and isinstance(t2, _PY_NUMBERS):
        arr, scalar, scalar_first = t1, t2, False
    elif isinstance(t2, DNDarray) and isinstance(t1, _PY_NUMBERS):
        arr, scalar, scalar_first = t2, t1, True
    else:
        return None
    if arr.ndim == 0 or (arr.split is not None and arr.shape[arr.split] == 1):
        return None
    if not _fusable(arr):
        return None
    try:
        leaf = dispatch.scalar_leaf(scalar, types.heat_type_of(scalar).jax_type())
    except Exception:  # lint: allow H501(scalar outside canonical dtype range -> no fusion)
        return None  # e.g. int out of the canonical dtype's range
    src = arr._fusion_source
    args = (leaf, src) if scalar_first else (src, leaf)
    node = dispatch.make_node(operation, args, fn_kwargs)
    if (
        node is None
        or node.shape != arr._padded_shape
        or types.heat_type_is_complexfloating(node.dtype)
    ):
        return None
    return DNDarray.from_pending(node, arr.shape, arr.split, arr.device, arr.comm)


def __binary_op(
    operation: Callable,
    t1,
    t2,
    out: Optional[DNDarray] = None,
    where=True,
    fn_kwargs: Optional[dict] = None,
) -> DNDarray:
    """Generic distributed binary operation (_operations.py:22)."""
    fn_kwargs = fn_kwargs or {}
    if out is None and where is True:
        if not fn_kwargs:
            planar = _try_planar_binary(operation, t1, t2)
            if planar is not None:
                return planar._propagate_layout_from(t1, t2)
        fast = _try_scalar_fast(operation, t1, t2, fn_kwargs)
        if fast is not None:
            return fast._propagate_layout_from(t1, t2)
    ref = t1 if isinstance(t1, DNDarray) else (t2 if isinstance(t2, DNDarray) else None)
    if ref is None:
        t1 = _as_dndarray(t1)
        ref = t1
    t1 = _as_dndarray(t1, ref)
    t2 = _as_dndarray(t2, ref)
    if t1.comm != t2.comm:
        raise NotImplementedError("operands must share a communication context")

    out_shape = broadcast_shape(t1.shape, t2.shape)

    # fast paths: (a) identical layout, no broadcasting — operate on the
    # padded buffers; (b) one operand is 0-d — it broadcasts elementwise
    # against the carrier's padded buffer (pad rows stay garbage-in,
    # garbage-out).  Both defer as a pending fusion node when possible:
    # the chain compiles as one executable at its first forcing boundary.
    same_layout = t1.shape == t2.shape == out_shape and t1.split == t2.split
    scalar_fast = not same_layout and (
        (t1.ndim == 0 and t1.split is None and t2.shape == out_shape
         and (t2.split is None or t2.shape[t2.split] != 1))
        or (t2.ndim == 0 and t2.split is None and t1.shape == out_shape
            and (t1.split is None or t1.shape[t1.split] != 1))
    )
    # (c) one operand has the output's shape and the other's padded buffer
    # broadcasts against it element for element (a row of statistics
    # against a table split along its rows): joins the chain, or takes the
    # general path below when it cannot be fused
    padded_fast = same_layout or scalar_fast
    broadcast = None if padded_fast else _broadcast_carrier(t1, t2, out_shape)
    carrier = broadcast if broadcast is not None else (t1 if t1.shape == out_shape else t2)
    node = None
    if (padded_fast or broadcast is not None) and _fusable(t1, t2):
        node = dispatch.make_node(
            operation, (_fusion_arg(t1), _fusion_arg(t2)), fn_kwargs
        )
        if node is not None and (
            node.shape != carrier._padded_shape
            or types.heat_type_is_complexfloating(node.dtype)
        ):
            node = None  # op degenerated the padded layout, or complex: eager path
    if padded_fast or node is not None:
        if node is not None:
            res = DNDarray.from_pending(
                node, out_shape, carrier.split, carrier.device, carrier.comm
            )
        else:
            a1 = t1.larray_padded if t1.shape == out_shape else t1._dense()
            a2 = t2.larray_padded if t2.shape == out_shape else t2._dense()
            result = dispatch.eager_apply(operation, (a1, a2), fn_kwargs)
            res = DNDarray(
                jax.device_put(result, carrier.comm.sharding(carrier.split)),
                out_shape,
                types.canonical_heat_type(result.dtype),
                carrier.split,
                carrier.device,
                carrier.comm,
            )
    else:
        out_split = _out_split_binary(t1, t2, out_shape)
        result = dispatch.eager_apply(
            operation, (t1._dense(), t2._dense()), fn_kwargs
        )
        res = DNDarray.from_dense(result, out_split, t1.device, t1.comm)

    if where is not True and where is not None:
        where_nd = _as_dndarray(where, ref)
        base = out if out is not None else None
        base_dense = (
            base._dense() if base is not None
            else jnp.zeros(out_shape, res.dtype.jax_type())
        )
        sel = jnp.where(where_nd._dense(), res._dense(), base_dense)
        res = DNDarray.from_dense(sel, res.split, res.device, res.comm)

    if out is not None:
        return store_out(res, out)
    # an active ragged layout survives elementwise ops (lhs-first)
    return res._propagate_layout_from(t1, t2)


def _broadcast_carrier(t1: DNDarray, t2: DNDarray, out_shape) -> Optional[DNDarray]:
    """The operand that carries a broadcasting binary op's layout, when the
    other's PADDED buffer broadcasts against the carrier's padded buffer as
    it stands: the carrier has the output's shape, and the other is either
    replicated with extent 1 (or no axis) where the carrier is split, or
    split along the same right-aligned axis with the same extent (a column
    of row norms against the table).  None: take the general path."""
    for c, o in ((t1, t2), (t2, t1)):
        if c.shape != tuple(out_shape) or not 1 <= o.ndim <= c.ndim:
            continue
        if c.split is None:
            if o.split is None:
                return c
            continue
        if c.shape[c.split] == 1:
            continue
        ax = c.split - (c.ndim - o.ndim)  # the other's axis under the carrier's split
        if o.split is None:
            if ax < 0 or o.shape[ax] == 1:
                return c
        elif o.split == ax and o.shape[ax] == c.shape[c.split]:
            return c
    return None


def _fusable(*operands: DNDarray) -> bool:
    """Whether these operands may ride the lazy fusion path: fusion on,
    no planar storage, no complex dtypes (complex elementwise ops stay
    on the plain cached-executable path; fusion has only ever been
    exercised on real dtypes)."""
    if not dispatch.fusion_enabled():
        return False
    for t in operands:
        if t._planar is not None or types.heat_type_is_complexfloating(t.dtype):
            return False
    return True


def _fusion_arg(t: DNDarray):
    """The fused-program operand for ``t``: its pending chain or padded
    buffer for layout carriers, its dense 0-d value for scalars."""
    if t.ndim == 0:
        return t._dense()
    return t._fusion_source


def __local_op(
    operation: Callable,
    x: DNDarray,
    out: Optional[DNDarray] = None,
    no_cast: bool = False,
    **kwargs,
) -> DNDarray:
    """Element-wise unary op (_operations.py:331): one jnp call on the padded
    buffer; sharding (and thus distribution) is preserved."""
    if not isinstance(x, DNDarray):
        raise TypeError(f"expected x to be a DNDarray, but was {type(x)}")
    if x._planar is not None and out is None and not kwargs:
        # ops that decompose plane-wise stay on the mesh
        if operation is jnp.negative:
            re, im = x._planar
            return DNDarray.from_planar(
                -re, -im, x.shape, x.split, x.device, x.comm
            )._propagate_layout_from(x)
        if operation is jnp.positive:
            re, im = x._planar  # fresh wrapper: +x must not alias x
            return DNDarray.from_planar(
                re, im, x.shape, x.split, x.device, x.comm
            )._propagate_layout_from(x)
    needs_cast = not no_cast and not types.heat_type_is_inexact(x.dtype)
    node = None
    if _fusable(x):
        src = x._fusion_source
        if needs_cast:
            src = dispatch.cast_node(src, jnp.float32)
        node = dispatch.make_node(operation, (src,), kwargs) if src is not None else None
        if node is not None and (
            node.shape != x._padded_shape
            or types.heat_type_is_complexfloating(node.dtype)
        ):
            node = None  # shape-changing or complex-producing op: eager
    if node is not None:
        res = DNDarray.from_pending(node, x.shape, x.split, x.device, x.comm)
    else:
        arr = x.larray_padded
        if needs_cast:
            arr = arr.astype(jnp.float32)
        result = dispatch.eager_apply(operation, (arr,), kwargs)
        res = DNDarray(
            result,
            x.shape,
            types.canonical_heat_type(result.dtype),
            x.split,
            x.device,
            x.comm,
        )
    if out is not None:
        return store_out(res, out)
    return res._propagate_layout_from(x)


def __reduce_op(
    operation: Callable,
    x: DNDarray,
    axis,
    neutral: Optional[Scalar],
    out: Optional[DNDarray] = None,
    keepdims: bool = False,
    **kwargs,
) -> DNDarray:
    """Generic reduction (_operations.py:404).

    The reference computes a local partial then Allreduces with a custom MPI
    op when the split axis is reduced; here the global jnp reduction already
    spans shards, so the only distribution work is (a) masking padding with
    the neutral element when the split axis participates, and (b) tracking
    the output split index.
    """
    if not isinstance(x, DNDarray):
        raise TypeError(f"expected x to be a DNDarray, but was {type(x)}")
    axis = sanitize_axis(x.shape, axis)
    axes: Tuple[int, ...]
    if axis is None:
        axes = tuple(range(x.ndim))
    elif isinstance(axis, tuple):
        axes = axis
    else:
        axes = (axis,)

    split_reduced = x.split is not None and x.split in axes
    mask = None
    if split_reduced and x._pad > 0:
        if neutral is None:
            arr = x._dense()
            result = operation(arr, axis=(axis if axis is not None else None), keepdims=keepdims, **kwargs)
            out_split = _reduced_split(x.split, axes, keepdims, reduced=True)
            res = DNDarray.from_dense(result, out_split, x.device, x.comm)
            return _finalize_reduce(res, out)
        mask = (x.split, x.shape[x.split], neutral)

    # a reduction is a fusion boundary: any pending elementwise chain,
    # the neutral-element pad masking, and the reduction itself compile
    # as ONE cached executable
    red_kwargs = dict(kwargs)
    red_kwargs["axis"] = axis if axis is not None else None
    red_kwargs["keepdims"] = keepdims
    if x._planar is None and not types.heat_type_is_complexfloating(x.dtype):
        result = dispatch.chain_apply(operation, x._fusion_source, red_kwargs, mask=mask)
    else:
        arr = x._masked(neutral) if mask is not None else x.larray_padded
        result = operation(arr, **red_kwargs)

    return _finalize_reduce(_wrap_reduced(result, x, axes, keepdims), out)


def _wrap_reduced(result, x: DNDarray, axes: Tuple[int, ...], keepdims: bool) -> DNDarray:
    """The ``DNDarray`` of ``x``'s padded buffer reduced over ``axes``."""
    if x.split is None or x.split in axes:
        return DNDarray.from_dense(result, None, x.device, x.comm)
    # split axis survives; result is still canonically padded along it
    new_split = _reduced_split(x.split, axes, keepdims, reduced=False)
    return DNDarray(
        jax.device_put(result, x.comm.sharding(new_split)),
        _reduced_shape(x.shape, axes, keepdims),
        types.canonical_heat_type(result.dtype),
        new_split,
        x.device,
        x.comm,
    )


def _finalize_reduce(res: DNDarray, out: Optional[DNDarray]) -> DNDarray:
    if out is not None:
        return store_out(res, out)
    return res


def _reduced_shape(shape, axes, keepdims) -> Tuple[int, ...]:
    if keepdims:
        return tuple(1 if d in axes else s for d, s in enumerate(shape))
    return tuple(s for d, s in enumerate(shape) if d not in axes)


def _reduced_split(split, axes, keepdims, reduced: bool) -> Optional[int]:
    if reduced:
        return None
    if keepdims:
        return split
    return split - sum(1 for a in axes if a < split)


def __cum_op(
    operation: Callable,
    x: DNDarray,
    axis: int,
    neutral: Scalar,
    out: Optional[DNDarray] = None,
    dtype=None,
) -> DNDarray:
    """Cumulative op along an axis (_operations.py:230).

    The reference does a local cumop, an Exscan of totals and a final local
    combine; here a single jnp cum-op over the (neutral-masked) global array
    compiles to the same scan pattern.
    """
    if not isinstance(x, DNDarray):
        raise TypeError(f"expected x to be a DNDarray, but was {type(x)}")
    axis = sanitize_axis(x.shape, axis)
    if axis is None:
        raise NotImplementedError("cumulative ops over flattened arrays: pass an int axis")
    mask = (x.split, x.shape[axis], neutral) if (x.split == axis and x._pad > 0) else None
    # scan boundary: pending chain + pad masking + cum-op fuse into one
    # cached executable (the reference's local-cumop + Exscan + combine)
    if x._planar is None and not types.heat_type_is_complexfloating(x.dtype):
        result = dispatch.chain_apply(operation, x._fusion_source, {"axis": axis}, mask=mask)
    else:
        arr = x._masked(neutral) if mask is not None else x.larray_padded
        result = operation(arr, axis=axis)
    if dtype is not None:
        result = result.astype(types.canonical_heat_type(dtype).jax_type())
    res = DNDarray(
        jax.device_put(result, x.comm.sharding(x.split)),
        x.shape,
        types.canonical_heat_type(result.dtype),
        x.split,
        x.device,
        x.comm,
    )
    if out is not None:
        return store_out(res, out)
    return res._propagate_layout_from(x)
