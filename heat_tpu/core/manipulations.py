"""Shape/layout manipulations, analog of heat/core/manipulations.py (41 funcs).

The reference implements each of these with bespoke message passing
(pairwise chunk-matched concatenate :392, mirror-rank flip :1052, the
flatten/redistribute/reshape pipeline :2018, cyclic-shift roll :2225, the
parallel sample-sort :2497, gather-based unique :3271, Alltoallw resplit
:3712, custom topk merge op :4330).  Here each is a jnp call on the global
sharded array — XLA emits the equivalent all-to-alls / permutes — plus
split bookkeeping for the result.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map

from ..parallel.comm import sanitize_comm
from . import types
from .dndarray import DNDarray
from .stride_tricks import sanitize_axis, sanitize_shape

__all__ = [
    "balance",
    "broadcast_arrays",
    "broadcast_to",
    "collect",
    "column_stack",
    "concatenate",
    "diag",
    "diagonal",
    "dsplit",
    "expand_dims",
    "flatten",
    "flip",
    "fliplr",
    "flipud",
    "hsplit",
    "hstack",
    "moveaxis",
    "pad",
    "ravel",
    "redistribute",
    "repeat",
    "reshape",
    "resplit",
    "roll",
    "rot90",
    "row_stack",
    "shape",
    "sort",
    "split",
    "squeeze",
    "stack",
    "swapaxes",
    "tile",
    "topk",
    "unfold",
    "unique",
    "vsplit",
    "vstack",
]


def balance(array: DNDarray, copy: bool = False) -> DNDarray:
    """Out-of-place balance (manipulations.py:68) — identity under the
    canonical distribution."""
    from .memory import copy as _copy

    return _copy(array) if copy else array


def broadcast_arrays(*arrays: DNDarray) -> List[DNDarray]:
    """Broadcast arrays against each other (manipulations.py:130)."""
    if not arrays:
        return []
    shapes = [a.shape for a in arrays]
    out_shape = tuple(np.broadcast_shapes(*shapes))
    return [broadcast_to(a, out_shape) for a in arrays]


def broadcast_to(x: DNDarray, shape) -> DNDarray:
    """Broadcast to a new shape (manipulations.py:185)."""
    shape = sanitize_shape(shape)
    result = jnp.broadcast_to(x._dense(), shape)
    if x.split is None:
        out_split = None
    else:
        out_split = x.split + (len(shape) - x.ndim)
    return DNDarray.from_dense(result, out_split, x.device, x.comm)


def collect(arr: DNDarray, target_rank: int = 0) -> DNDarray:
    """Replicate the full array (manipulations.py:240 analog)."""
    return resplit(arr, None)


def column_stack(arrays: Sequence[DNDarray]) -> DNDarray:
    """Stack 1-D/2-D arrays as columns (manipulations.py:295)."""
    prepared = []
    for a in arrays:
        d = a._dense() if isinstance(a, DNDarray) else jnp.asarray(a)
        if d.ndim == 1:
            d = d[:, None]
        prepared.append(d)
    ref = _first_dnd(arrays)
    result = jnp.concatenate(prepared, axis=1)
    return DNDarray.from_dense(result, ref.split if ref is not None else None, _dev(ref), _comm(ref))


def _first_dnd(arrays):
    for a in arrays:
        if isinstance(a, DNDarray):
            return a
    return None


def _dev(ref):
    return ref.device if ref is not None else None


def _comm(ref):
    return ref.comm if ref is not None else None


def concatenate(arrays: Sequence[DNDarray], axis: int = 0) -> DNDarray:
    """Join arrays along an existing axis (manipulations.py:392)."""
    if not isinstance(arrays, (list, tuple)):
        raise TypeError("arrays must be a list or a tuple")
    if len(arrays) == 0:
        raise ValueError("need at least one array to concatenate")
    ref = _first_dnd(arrays)
    dense = [a._dense() if isinstance(a, DNDarray) else jnp.asarray(a) for a in arrays]
    axis = sanitize_axis(dense[0].shape, axis)
    # dtype promotion across inputs (reference promotes pairwise)
    out_dtype = dense[0].dtype
    for d in dense[1:]:
        out_dtype = jnp.promote_types(out_dtype, d.dtype)
    dense = [d.astype(out_dtype) for d in dense]
    result = jnp.concatenate(dense, axis=axis)
    split = ref.split if ref is not None else None
    return DNDarray.from_dense(result, split, _dev(ref), _comm(ref))


def diag(a: DNDarray, offset: int = 0) -> DNDarray:
    """Extract or construct a diagonal (manipulations.py:580)."""
    if a.ndim not in (1, 2):
        raise ValueError(f"input must be 1- or 2-dimensional, got {a.ndim}-d")
    if a.ndim == 1:
        result = jnp.diag(a._dense(), k=offset)
        split = 0 if a.split is not None else None
        return DNDarray.from_dense(result, split, a.device, a.comm)
    return diagonal(a, offset=offset)


def diagonal(a: DNDarray, offset: int = 0, dim1: int = 0, dim2: int = 1) -> DNDarray:
    """Diagonal of a matrix / batch (manipulations.py:672)."""
    result = jnp.diagonal(a._dense(), offset=offset, axis1=dim1, axis2=dim2)
    split = None
    if a.split is not None and a.split not in (dim1, dim2):
        split = a.split - sum(1 for d in (dim1, dim2) if d < a.split)
    elif a.split is not None:
        split = result.ndim - 1
    return DNDarray.from_dense(result, split, a.device, a.comm)


def dsplit(x: DNDarray, indices_or_sections) -> List[DNDarray]:
    """Split along axis 2 (manipulations.py:772)."""
    if x.ndim < 3:
        raise ValueError("dsplit only works on arrays of 3 or more dimensions")
    return split(x, indices_or_sections, 2)


def expand_dims(a: DNDarray, axis: int) -> DNDarray:
    """Insert a size-1 axis (manipulations.py:824)."""
    axis = sanitize_axis(tuple(a.shape) + (1,), axis)
    result = jnp.expand_dims(a._dense(), axis)
    split = a.split
    if split is not None and axis <= split:
        split += 1
    return DNDarray.from_dense(result, split, a.device, a.comm)


def flatten(a: DNDarray) -> DNDarray:
    """1-D copy of the array (manipulations.py:891)."""
    result = a._dense().reshape(-1)
    return DNDarray.from_dense(result, 0 if a.split is not None else None, a.device, a.comm)


def flip(a: DNDarray, axis=None) -> DNDarray:
    """Reverse element order along axes (manipulations.py:1052)."""
    axis = sanitize_axis(a.shape, axis)
    result = jnp.flip(a._dense(), axis=axis)
    return DNDarray.from_dense(result, a.split, a.device, a.comm)


def fliplr(a: DNDarray) -> DNDarray:
    """Flip along axis 1 (manipulations.py:1118)."""
    return flip(a, 1)


def flipud(a: DNDarray) -> DNDarray:
    """Flip along axis 0 (manipulations.py:1155)."""
    return flip(a, 0)


def hsplit(x: DNDarray, indices_or_sections) -> List[DNDarray]:
    """Split along axis 1 (axis 0 for 1-D) (manipulations.py:1192)."""
    if x.ndim < 2:
        return split(x, indices_or_sections, 0)
    return split(x, indices_or_sections, 1)


def hstack(arrays: Sequence[DNDarray]) -> DNDarray:
    """Stack horizontally (manipulations.py:1255)."""
    a0 = arrays[0]
    nd = a0.ndim if isinstance(a0, DNDarray) else np.ndim(a0)
    return concatenate(arrays, axis=0 if nd == 1 else 1)


def moveaxis(x: DNDarray, source, destination) -> DNDarray:
    """Move axes to new positions (manipulations.py:1301)."""
    if isinstance(source, int):
        source = (source,)
    if isinstance(destination, int):
        destination = (destination,)
    source = tuple(sanitize_axis(x.shape, s) for s in source)
    destination = tuple(sanitize_axis(x.shape, d) for d in destination)
    if len(source) != len(destination):
        raise ValueError("source and destination arguments must have the same number of elements")
    perm = [n for n in range(x.ndim) if n not in source]
    for dest, src in sorted(zip(destination, source)):
        perm.insert(dest, src)
    from .linalg import basics

    return basics.transpose(x, perm)


#: numpy's mode -> accepted keyword table (np.pad docs); forwarding an
#: unrelated kwarg silently changes nothing, so it is rejected loudly
_PAD_MODE_KWARGS = {
    "constant": {"constant_values"},
    "edge": set(),
    "empty": set(),
    "linear_ramp": {"end_values"},
    "maximum": {"stat_length"},
    "mean": {"stat_length"},
    "median": {"stat_length"},
    "minimum": {"stat_length"},
    "reflect": {"reflect_type"},
    "symmetric": {"reflect_type"},
    "wrap": set(),
}


def pad(array: DNDarray, pad_width, mode: str = "constant", constant_values=0, **kwargs) -> DNDarray:
    """Pad an array (manipulations.py:1352).

    Mode-specific keywords (``reflect_type``, ``stat_length``,
    ``end_values``, ...) forward to ``jnp.pad`` after validation against
    the mode, matching ``np.pad``'s contract."""
    if callable(mode):
        result = jnp.pad(array._dense(), pad_width, mode=mode, **kwargs)
        return DNDarray.from_dense(result, array.split, array.device, array.comm)
    allowed = _PAD_MODE_KWARGS.get(mode)
    if allowed is None:
        raise ValueError(f"mode '{mode}' is not supported")
    if mode == "constant":
        kwargs.setdefault("constant_values", constant_values)
    unexpected = set(kwargs) - allowed
    if unexpected:
        raise ValueError(
            f"unsupported keyword arguments for mode '{mode}': {sorted(unexpected)}"
        )
    result = jnp.pad(array._dense(), pad_width, mode=mode, **kwargs)
    return DNDarray.from_dense(result, array.split, array.device, array.comm)


def ravel(a: DNDarray) -> DNDarray:
    """Flatten view (manipulations.py:1620)."""
    return flatten(a)


def redistribute(arr: DNDarray, lshape_map=None, target_map=None) -> DNDarray:
    """Out-of-place redistribute (manipulations.py:1730): a copy carrying
    the requested (possibly ragged) target layout."""
    from .memory import copy as _copy

    return _copy(arr).redistribute_(lshape_map=lshape_map, target_map=target_map)


def repeat(a: DNDarray, repeats, axis: Optional[int] = None) -> DNDarray:
    """Repeat elements (manipulations.py:1780)."""
    if isinstance(repeats, DNDarray):
        repeats = repeats._dense()
    elif isinstance(repeats, (list, tuple, np.ndarray)):
        repeats = jnp.asarray(repeats)
    result = jnp.repeat(a._dense(), repeats, axis=axis)
    if axis is None:
        split = 0 if a.split is not None else None
    else:
        split = a.split
    return DNDarray.from_dense(result, split, a.device, a.comm)


def reshape(a: DNDarray, *shape, new_split: Optional[int] = None) -> DNDarray:
    """Reshape to a new global shape (manipulations.py:2018).

    The reference pipeline (resplit to 0, local flatten, redistribute to
    target counts, local reshape, resplit) is a single global jnp.reshape
    under sharding — XLA emits the all-to-all.
    """
    if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
        shape = tuple(shape[0])
    shape = tuple(int(s) for s in shape)
    if -1 in shape:
        known = 1
        for s in shape:
            if s != -1:
                known *= s
        shape = tuple(a.size // known if s == -1 else s for s in shape)
    if int(np.prod(shape)) != a.size:
        raise ValueError(f"cannot reshape array of size {a.size} into shape {shape}")
    result = a._dense().reshape(shape)
    if new_split is None:
        new_split = a.split if a.split is not None and a.split < len(shape) else (
            0 if a.split is not None else None
        )
    return DNDarray.from_dense(result, sanitize_axis(shape, new_split), a.device, a.comm)


def resplit(arr: DNDarray, axis: Optional[int] = None) -> DNDarray:
    """Out-of-place resplit (manipulations.py:3633)."""
    return arr.resplit(axis)


def roll(x: DNDarray, shift, axis=None) -> DNDarray:
    """Cyclic shift (manipulations.py:2225); the reference's wrap-block
    send/recv is XLA's collective-permute here."""
    result = jnp.roll(x._dense(), shift, axis=axis)
    return DNDarray.from_dense(result, x.split, x.device, x.comm)


def rot90(m: DNDarray, k: int = 1, axes=(0, 1)) -> DNDarray:
    """Rotate in the plane of two axes (manipulations.py:2298)."""
    result = jnp.rot90(m._dense(), k=k, axes=axes)
    split = m.split
    if split in axes and k % 2 == 1:
        split = axes[0] if split == axes[1] else axes[1]
    return DNDarray.from_dense(result, split, m.device, m.comm)


def row_stack(arrays: Sequence[DNDarray]) -> DNDarray:
    """Stack rows (manipulations.py:2407)."""
    prepared = []
    for a in arrays:
        d = a._dense() if isinstance(a, DNDarray) else jnp.asarray(a)
        if d.ndim == 1:
            d = d[None, :]
        prepared.append(d)
    ref = _first_dnd(arrays)
    result = jnp.concatenate(prepared, axis=0)
    return DNDarray.from_dense(result, ref.split if ref is not None else None, _dev(ref), _comm(ref))


vstack = row_stack


def shape(a: DNDarray) -> Tuple[int, ...]:
    """Global shape (manipulations.py:2487)."""
    return a.shape


def sort(a: DNDarray, axis: int = -1, descending: bool = False, out=None):
    """Sort along an axis (manipulations.py:2497).

    The reference hand-writes a parallel sample-sort (local sort, global
    pivots, Alltoallv, merge); the global jnp.sort over the sharded array
    compiles to XLA's distributed sort.  Returns (values, indices) like the
    reference.
    """
    axis = sanitize_axis(a.shape, axis)

    from .sample_sort import sample_sort_along, supports_sample_sort

    if supports_sample_sort(a, axis, descending):
        res_v, res_i = sample_sort_along(a, axis, descending)
        if out is not None:
            from .sanitation import sanitize_out

            sanitize_out(out, res_v.shape, res_v.split, res_v.device)
            src = res_v.astype(out.dtype)
            if out.split == src.split:
                # same canonical layout — adopt the PSRS backing directly
                out._replace(src.larray_padded)
            else:
                # out has a different split: one reshard via resplit
                out._replace(src.resplit(out.split).larray_padded)
            return out, res_i
        return res_v, res_i

    dense = a._dense()
    idx = jnp.argsort(dense, axis=axis, descending=descending, stable=True)
    values = jnp.take_along_axis(dense, idx, axis=axis)
    res_v = DNDarray.from_dense(values, a.split, a.device, a.comm)
    res_i = DNDarray.from_dense(idx.astype(types.canonical_dtype(jnp.int64)), a.split, a.device, a.comm)
    if out is not None:
        from .sanitation import sanitize_out

        sanitize_out(out, res_v.shape, res_v.split, res_v.device)
        out._replace(DNDarray.from_dense(values.astype(out.dtype.jax_type()), out.split, out.device, out.comm).larray_padded)
        return out, res_i
    return res_v, res_i


def split(x: DNDarray, indices_or_sections, axis: int = 0) -> List[DNDarray]:
    """Split into sub-arrays (manipulations.py:2751)."""
    axis = sanitize_axis(x.shape, axis)
    if isinstance(indices_or_sections, DNDarray):
        indices_or_sections = np.asarray(indices_or_sections._dense()).tolist()
    if isinstance(indices_or_sections, (list, tuple, np.ndarray)):
        parts = jnp.split(x._dense(), np.asarray(indices_or_sections), axis=axis)
    else:
        n = int(indices_or_sections)
        if x.shape[axis] % n != 0:
            raise ValueError("array split does not result in an equal division")
        parts = jnp.split(x._dense(), n, axis=axis)
    return [DNDarray.from_dense(p, x.split, x.device, x.comm) for p in parts]


def squeeze(x: DNDarray, axis=None) -> DNDarray:
    """Remove size-1 axes (manipulations.py:2876)."""
    ax = sanitize_axis(x.shape, axis)
    if ax is not None:
        axes = ax if isinstance(ax, tuple) else (ax,)
        for a in axes:
            if x.shape[a] != 1:
                raise ValueError(f"cannot select an axis to squeeze out which has size not equal to one, got axis {a}")
    else:
        axes = tuple(d for d, s in enumerate(x.shape) if s == 1)
    result = jnp.squeeze(x._dense(), axis=axes if axes else None)
    split = x.split
    if split is not None:
        if split in axes:
            split = None
        else:
            split -= sum(1 for a in axes if a < split)
    return DNDarray.from_dense(result, split, x.device, x.comm)


def stack(arrays: Sequence[DNDarray], axis: int = 0, out=None) -> DNDarray:
    """Join along a NEW axis (manipulations.py:3088)."""
    ref = _first_dnd(arrays)
    dense = [a._dense() if isinstance(a, DNDarray) else jnp.asarray(a) for a in arrays]
    result = jnp.stack(dense, axis=axis)
    split = ref.split if ref is not None else None
    axis_n = axis % result.ndim
    if split is not None and axis_n <= split:
        split += 1
    res = DNDarray.from_dense(result, split, _dev(ref), _comm(ref))
    if out is not None:
        from .sanitation import sanitize_out

        sanitize_out(out, res.shape, res.split, res.device)
        out._replace(res.larray_padded)
        return out
    return res


def swapaxes(x: DNDarray, axis1: int, axis2: int) -> DNDarray:
    """Interchange two axes (manipulations.py:3223)."""
    from .linalg import basics

    axis1 = sanitize_axis(x.shape, axis1)
    axis2 = sanitize_axis(x.shape, axis2)
    perm = list(range(x.ndim))
    perm[axis1], perm[axis2] = perm[axis2], perm[axis1]
    return basics.transpose(x, perm)


def tile(x: DNDarray, reps) -> DNDarray:
    """Tile the array (manipulations.py:4050)."""
    if isinstance(reps, DNDarray):
        reps = np.asarray(reps._dense()).tolist()
    result = jnp.tile(x._dense(), reps)
    split = x.split
    if split is not None:
        split += result.ndim - x.ndim
    return DNDarray.from_dense(result, split, x.device, x.comm)


def topk(a: DNDarray, k: int, dim: int = -1, largest: bool = True, sorted: bool = True, out=None):
    """Top-k values and indices (manipulations.py:4175).

    Along a split 1-D axis the reference's custom MPI merge op becomes a
    shard_map merge: each shard takes a local top-k, the p*k candidates
    all_gather (tiny), and a replicated final top-k picks the winners —
    GSPMD's own lowering would all-gather the full array instead."""
    dim = sanitize_axis(a.shape, dim)
    _np_dt = np.dtype(a.dtype.jax_type())
    if (
        a.ndim == 1
        and a.split == 0
        and dim == 0
        and a.comm.size > 1
        and 0 < k <= a.shape[0]
        and out is None
        # int "smallest" needs a negation that overflows at INT_MIN, and
        # bool has no iinfo sentinel: both keep the dense path
        and (
            np.issubdtype(_np_dt, np.floating)
            or (largest and _np_dt != np.dtype(bool))
        )
    ):
        block = a.larray_padded.shape[0] // a.comm.size
        vals, idx = _topk_merge_fn(a.comm, int(k), bool(largest), a.shape[0], block)(
            a.larray_padded
        )
        return (
            DNDarray.from_dense(vals, None, a.device, a.comm),
            DNDarray.from_dense(idx.astype(types.canonical_dtype(jnp.int64)), None, a.device, a.comm),
        )
    dense = a._dense()
    moved = jnp.moveaxis(dense, dim, -1)
    if largest:
        vals, idx = jax.lax.top_k(moved, k)
    else:
        vals, idx = jax.lax.top_k(-moved, k)
        vals = -vals
    vals = jnp.moveaxis(vals, -1, dim)
    idx = jnp.moveaxis(idx, -1, dim)
    res_v = DNDarray.from_dense(vals, a.split, a.device, a.comm)
    res_i = DNDarray.from_dense(idx.astype(types.canonical_dtype(jnp.int64)), a.split, a.device, a.comm)
    if out is not None:
        if not (isinstance(out, tuple) and len(out) == 2):
            raise TypeError("out must be a (values, indices) tuple of DNDarrays")
        out[0]._replace(res_v.larray_padded)
        out[1]._replace(res_i.larray_padded)
        return out[0], out[1]
    return res_v, res_i


@functools.lru_cache(maxsize=64)
def _topk_merge_fn(comm, k: int, largest: bool, n_true: int, block: int):
    """Jitted, cached distributed top-k merge executable."""
    from jax.sharding import PartitionSpec as P

    axis = comm.axis_name

    def body(a_loc):
        idx = jax.lax.axis_index(axis)
        gpos = idx * block + jnp.arange(block)
        if jnp.issubdtype(a_loc.dtype, jnp.floating):
            sentinel = jnp.array(-jnp.inf if largest else jnp.inf, a_loc.dtype)
        else:
            info = jnp.iinfo(a_loc.dtype)
            sentinel = jnp.array(info.min if largest else info.max, a_loc.dtype)
        x = jnp.where(gpos < n_true, a_loc, sentinel)  # padding never wins
        key = x if largest else -x  # int smallest is gated to the dense path
        kk = min(k, block)
        lv, li = jax.lax.top_k(key, kk)
        gi = idx * block + li
        cand_v = jax.lax.all_gather(lv, axis, axis=0, tiled=True)  # (p*kk,)
        cand_i = jax.lax.all_gather(gi, axis, axis=0, tiled=True)
        fv, fi = jax.lax.top_k(cand_v, k)
        vals = fv if largest else -fv
        return vals, cand_i[fi]

    return jax.jit(
        _shard_map(
            body,
            mesh=comm.mesh,
            in_specs=P(axis),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )


def unfold(a: DNDarray, axis: int, size: int, step: int = 1) -> DNDarray:
    """Sliding windows along an axis (manipulations.py:3484).

    The reference fetches a halo of size-1 rows from the next rank
    (:3546); XLA's gather handles the shard boundary here.
    """
    axis = sanitize_axis(a.shape, axis)
    if size < 1:
        raise ValueError("size must be >= 1")
    if step < 1:
        raise ValueError("step must be >= 1")
    n = a.shape[axis]
    if size > n:
        raise ValueError(f"maximum size for DNDarray at axis {axis} is {n} but size is {size}")
    starts = jnp.arange(0, n - size + 1, step)
    dense = jnp.moveaxis(a._dense(), axis, 0)
    windows = jax.vmap(
        lambda s: jax.lax.dynamic_slice_in_dim(dense, s, size, axis=0)
    )(starts)
    # windows: (n_windows, size, ...); reference layout: window axis at
    # `axis`, window contents appended as last dimension
    windows = jnp.moveaxis(windows, 1, -1)  # (n_windows, ..., size)
    windows = jnp.moveaxis(windows, 0, axis)
    split = a.split
    return DNDarray.from_dense(windows, split, a.device, a.comm)


def unique(a: DNDarray, sorted: bool = False, return_inverse: bool = False, axis=None):
    """Unique elements (manipulations.py:3271): local unique + gather in the
    reference, a global jnp.unique here (eager => dynamic output shape OK).

    Large 1-D split arrays ride the PSRS sorted distribution: adjacent
    diff on the sharded sorted values (the shard boundary is one implicit
    halo, not a gather) + a take of only the distinct positions."""
    if axis is None and a.ndim == 1 and a.split == 0 and not return_inverse:
        from .sample_sort import sample_sort_1d, supports_sample_sort

        if supports_sample_sort(a, 0, False):
            v, _ = sample_sort_1d(a)
            vd = v._dense()
            neq = vd[1:] != vd[:-1]
            if jnp.issubdtype(vd.dtype, jnp.floating):
                # NaN != NaN — collapse the sorted-last NaN run to one
                # entry like jnp.unique/numpy do
                neq = neq & ~(jnp.isnan(vd[1:]) & jnp.isnan(vd[:-1]))
            flags = jnp.concatenate([jnp.ones((1,), bool), neq])
            cnt = int(jnp.sum(flags))
            idx = jnp.nonzero(flags, size=cnt)[0]
            vals = jnp.take(vd, idx)
            return DNDarray.from_dense(vals, 0, a.device, a.comm)
    dense = a._dense()
    if axis is not None:
        axis = sanitize_axis(a.shape, axis)
    if return_inverse:
        vals, inverse = jnp.unique(dense, return_inverse=True, axis=axis)
        split = 0 if a.split is not None and vals.ndim > 0 else None
        return (
            DNDarray.from_dense(vals, split, a.device, a.comm),
            DNDarray.from_dense(inverse, None, a.device, a.comm),
        )
    vals = jnp.unique(dense, axis=axis)
    split = 0 if a.split is not None and vals.ndim > 0 else None
    return DNDarray.from_dense(vals, split, a.device, a.comm)


def vsplit(x: DNDarray, indices_or_sections) -> List[DNDarray]:
    """Split along axis 0 (manipulations.py:4415)."""
    if x.ndim < 2:
        raise ValueError("vsplit only works on arrays of 2 or more dimensions")
    return split(x, indices_or_sections, 0)
