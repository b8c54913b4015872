"""User-level fusion: compile a DNDarray -> DNDarray function to one XLA
program.

SURVEY.md build-plan decision 2: the library is eager (every op dispatches
a cached executable) so sklearn-style loops just work, "offer ht.jit-style
fusion on top".  Since the dispatch layer landed (core/dispatch.py), the
eager path itself routes ops through cached executables and lazily fuses
element-wise chains by DEFAULT — ``ht.jit`` remains the explicit tool for
fusing ACROSS non-elementwise boundaries (reductions, matmuls, whole
pipelines) into one program.  ``ht.jit`` traces the wrapped function
once per (structure, DNDarray shapes/dtypes/splits, static values), so a
whole pipeline of ops — elementwise chains, reductions, linalg — fuses
into a single device program with one dispatch.  Each eager dispatch
pays the host's launch latency, so fusing an n-op pipeline of small ops
saves about n-1 launches, and XLA can fuse across the op boundaries the
eager layer keeps.

Semantics and limits (the usual jax.jit contract, surfaced at this level):

* DNDarray arguments become traced values; everything else (ints, strings,
  shapes...) is STATIC — a new compilation per distinct value.
* The function must be functional over its DNDarray inputs.  Host syncs
  (``float(x)``, ``x.numpy()``, data-dependent Python control flow) raise
  jax's ConcretizationTypeError inside.  In-place updates to an ARGUMENT
  (``a += 1``, ``a[0] = ...``) do NOT raise — they rebind the traced
  value, so the result is correct but the caller's array is left
  unmodified (under eager execution the caller's array would mutate).
  Return what you change.
* Returned DNDarrays keep the split/device/comm they were constructed
  with inside the trace.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax

from .dndarray import DNDarray

__all__ = ["jit"]


class _ASpec:
    """Hashable stand-in for a DNDarray argument in the cache key."""

    __slots__ = ("shape", "dtype", "split", "device", "comm", "pshape", "pdtype")

    def __init__(self, x: DNDarray):
        self.shape = x.shape
        self.dtype = x.dtype
        self.split = x.split
        self.device = x.device
        self.comm = x.comm
        # metadata-only: a pending fusion chain must not be forced just
        # to build a cache key (core/dispatch.py)
        self.pshape = x._padded_shape
        self.pdtype = str(x._padded_dtype)

    def _key(self):
        return (
            self.shape, self.dtype, self.split, self.device, self.comm,
            self.pshape, self.pdtype,
        )

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, _ASpec) and self._key() == other._key()

    def rebuild(self, arr) -> DNDarray:
        return DNDarray(arr, self.shape, self.dtype, self.split, self.device, self.comm)


def jit(fn: Callable = None, **jit_kwargs) -> Callable:
    """Fuse a function over DNDarrays into one compiled program.

    ::

        @ht.jit
        def step(x, w):
            return ht.tanh(x @ w) - ht.mean(x, axis=0)

        y = step(a, b)     # one device dispatch, however many ops inside
    """
    if fn is None:
        return lambda f: jit(f, **jit_kwargs)

    # argument-indexed jax.jit options would be interpreted against the
    # internal flattened array-leaf signature, not the user's parameters —
    # silently donating/pinning the wrong argument.  Reject them.
    _positional = {
        "static_argnums", "static_argnames", "donate_argnums",
        "donate_argnames", "in_shardings", "out_shardings",
    }
    bad = _positional.intersection(jit_kwargs)
    if bad:
        raise TypeError(
            f"ht.jit does not accept argument-indexed jax.jit options "
            f"({sorted(bad)}): indices would refer to the internal flattened "
            f"signature, not your function's parameters"
        )

    cache = {}

    @functools.wraps(fn)
    def wrapper(*args: Any, **kwargs: Any):
        is_d = lambda x: isinstance(x, DNDarray)
        flat, treedef = jax.tree_util.tree_flatten((args, kwargs), is_leaf=is_d)
        arrays = [x.larray_padded for x in flat if is_d(x)]
        key_leaves = tuple(_ASpec(x) if is_d(x) else ("static", x) for x in flat)
        try:
            key = (treedef, key_leaves)
            hash(key)
        except TypeError:
            raise TypeError(
                "ht.jit arguments must be DNDarrays or hashable statics; "
                "got an unhashable non-array argument"
            ) from None

        from . import dispatch as _dispatch

        entry = cache.get(key)
        if entry is None:
            out_side = {}

            def inner(*arrs):
                it = iter(arrs)
                rebuilt = [
                    k.rebuild(next(it)) if isinstance(k, _ASpec) else k[1]
                    for k in key_leaves
                ]
                a2, k2 = jax.tree_util.tree_unflatten(treedef, rebuilt)
                out = fn(*a2, **k2)
                out_flat, out_tree = jax.tree_util.tree_flatten(out, is_leaf=is_d)
                out_side["tree"] = out_tree
                out_side["meta"] = [
                    (x.shape, x.dtype, x.split, x.device, x.comm) if is_d(x) else None
                    for x in out_flat
                ]
                return tuple(
                    x.larray_padded if is_d(x) else x for x in out_flat
                )

            entry = (jax.jit(inner, **jit_kwargs), out_side)
            cache[key] = entry

        compiled, out_side = entry
        # user-level fusion rides the same accounting as the transparent
        # dispatch layer: one compiled launch, however many ops inside
        _dispatch.record_external_dispatch()
        out_arrays = compiled(*arrays)
        rebuilt_out = [
            DNDarray(arr, *meta) if meta is not None else arr
            for arr, meta in zip(out_arrays, out_side["meta"])
        ]
        return jax.tree_util.tree_unflatten(out_side["tree"], rebuilt_out)

    return wrapper
