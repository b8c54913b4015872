"""Array creation routines, analog of heat/core/factories.py.

The reference materializes the full input on every MPI rank and slices out
the local chunk via ``comm.chunk`` (factories.py:149-482); here the global
array is built once on host and placed with the canonical NamedSharding
(``jax.device_put`` scatters the shards over ICI).  ``is_split`` ingestion
maps to ``jax.make_array_from_process_local_data``.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Sequence, Tuple, Type, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.comm import Communication, sanitize_comm
from . import types
from .devices import Device, sanitize_device
from .dndarray import DNDarray
from .stride_tricks import sanitize_axis, sanitize_shape

__all__ = [
    "arange",
    "array",
    "asarray",
    "empty",
    "empty_like",
    "eye",
    "from_partitioned",
    "from_partition_dict",
    "frombuffer",
    "fromfunction",
    "fromiter",
    "fromstring",
    "full",
    "full_like",
    "geomspace",
    "identity",
    "linspace",
    "logspace",
    "meshgrid",
    "ones",
    "ones_like",
    "zeros",
    "zeros_like",
]


def arange(*args, dtype=None, split=None, device=None, comm=None) -> DNDarray:
    """Evenly spaced values in [start, stop) (factories.py:41)."""
    num_args = len(args)
    if num_args == 1:
        start, stop, step = 0, args[0], 1
    elif num_args == 2:
        start, stop, step = args[0], args[1], 1
    elif num_args == 3:
        start, stop, step = args
    else:
        raise TypeError(f"arange takes 1 to 3 positional arguments, got {num_args}")

    if dtype is None:
        if all(isinstance(a, (int, np.integer)) for a in (start, stop, step)):
            dtype = types.int32
        else:
            dtype = types.float32
    dtype = types.canonical_heat_type(dtype)
    data = jnp.arange(start, stop, step, dtype=dtype.jax_type())
    return DNDarray.from_dense(data, sanitize_axis(data.shape, split), sanitize_device(device), sanitize_comm(comm))


def array(
    obj,
    dtype=None,
    copy: Optional[bool] = None,
    ndmin: int = 0,
    order: str = "C",
    split: Optional[int] = None,
    is_split: Optional[int] = None,
    device=None,
    comm=None,
) -> DNDarray:
    """Create a DNDarray from array-like data (factories.py:149-482).

    ``split`` distributes the (globally known) data along an axis;
    ``is_split`` declares that ``obj`` is this process's pre-distributed
    chunk along that axis.
    """
    if split is not None and is_split is not None:
        raise ValueError("split and is_split are mutually exclusive")
    if order not in ("C", "F"):
        raise ValueError(f"invalid memory layout order, expected 'C' or 'F', got {order!r}")
    comm = sanitize_comm(comm)
    device = sanitize_device(device)

    if isinstance(obj, DNDarray):
        if dtype is not None and types.canonical_heat_type(dtype) != obj.dtype:
            obj = obj.astype(dtype)
        if split is not None and obj.split != sanitize_axis(obj.shape, split):
            obj = obj.resplit(split)
        return obj

    if isinstance(obj, (jax.Array, jnp.ndarray)):
        data = obj
    else:
        # `copy=True` copies HERE: on the CPU `jnp.asarray` takes an aligned numpy buffer as it is, so a caller's
        # later write to `obj` would show in the array (by the luck of the allocation: tests/test_factories_grid.py)
        data = np.array(obj, order=order, copy=True) if copy else np.asarray(obj, order=order)

    if dtype is not None:
        dtype = types.canonical_heat_type(dtype)
        data = jnp.asarray(data, dtype=dtype.jax_type())
    else:
        # canonical defaults: python float data -> float32, ints -> int32,
        # unless the input already carries an explicit wider dtype
        # numpy scalars (np.generic) carry an explicit dtype just like
        # ndarrays do and keep it; only dtype-less python data narrows
        explicit = isinstance(obj, (np.ndarray, np.generic))
        if isinstance(data, np.ndarray) and data.dtype == np.float64 and not explicit:
            data = jnp.asarray(data, dtype=jnp.float32)
        elif isinstance(data, np.ndarray) and data.dtype == np.int64 and not explicit:
            data = jnp.asarray(data, dtype=jnp.int32)
        else:
            data = jnp.asarray(data)
        dtype = types.canonical_heat_type(data.dtype)

    while data.ndim < ndmin:
        data = data[jnp.newaxis]

    if is_split is not None:
        is_split = sanitize_axis(data.shape, is_split)
        if jax.process_count() == 1:
            return DNDarray.from_dense(data, is_split, device, comm)
        return _ingest_process_chunks(data, is_split, dtype, device, comm)

    split = sanitize_axis(data.shape, split)
    return DNDarray.from_dense(jnp.asarray(data), split, device, comm)


def _ingest_process_chunks(data, axis: int, dtype, device, comm) -> DNDarray:
    """Assemble a global DNDarray from each process's pre-distributed chunk.

    Multi-host ``is_split`` ingestion, the analog of the reference's
    allgather-based gshape inference (factories.py:382-428).  Two paths:

    1. aligned fast path — every process's chunk already coincides with its
       canonical block (e.g. it came from ``Communication.process_chunk``
       slab reads): host-local placement, zero communication;
    2. ragged general path — chunks of arbitrary extents: one host-level
       allgather rebuilds the global value on every process (the reference's
       ragged chunks are likewise host tensors before wrapping), then each
       local device shard is carved out of it.  Scales with the global array
       size on the host; large arrays should ingest via aligned slab reads.
    """
    from jax.experimental import multihost_utils

    nproc = jax.process_count()
    local = np.asarray(data)
    # Membership is globally known (the device list is the same on every
    # process), so a partial comm is detected on ALL processes before the
    # first collective — an asymmetric raise would leave the member
    # processes hanging in the allgather below.
    member_procs = {d.process_index for d in comm.devices}
    if member_procs != set(range(nproc)):
        raise RuntimeError(
            f"is_split ingestion requires every process to own devices in "
            f"the communication; members are processes {sorted(member_procs)} "
            f"of {nproc}"
        )
    # exchange chunk shapes; validate non-split dims agree (factories.py:406)
    shapes = multihost_utils.process_allgather(np.asarray(local.shape, dtype=np.int64))
    shapes = np.asarray(shapes).reshape(nproc, local.ndim)
    other = np.delete(shapes, axis, axis=1)
    if not (other == other[0]).all():
        raise ValueError(f"non-split dimensions must match across processes, got {shapes.tolist()}")
    exts = shapes[:, axis]
    offs = np.concatenate([[0], np.cumsum(exts)])
    total = int(offs[-1])
    gshape = local.shape[:axis] + (total,) + local.shape[axis + 1 :]
    sharding = comm.sharding(axis)
    padded_total = comm.padded_extent(total)
    padded_gshape = gshape[:axis] + (padded_total,) + gshape[axis + 1 :]
    per = padded_total // comm.size

    def _pad_rows(arr, rows):
        pad = rows - arr.shape[axis]
        if pad <= 0:
            return arr
        widths = [(0, pad) if d == axis else (0, 0) for d in range(arr.ndim)]
        return np.pad(arr, widths)

    # fast path: chunk == canonical process block everywhere, and each
    # process's devices cover one contiguous index range (so host-local data
    # tiles its shards exactly)
    aligned = comm.process_blocks_contiguous
    for q in range(nproc):
        if not aligned:
            break
        lo, lsh, _ = comm.process_chunk(gshape, axis, process=q)
        aligned = lo == int(offs[q]) and lsh[axis] == int(exts[q])
    if aligned:
        want = per * len(comm.local_participants)
        arr = jax.make_array_from_process_local_data(
            sharding, _pad_rows(local, want), padded_gshape
        )
        return DNDarray(arr, gshape, dtype, axis, device, comm)

    # general (ragged) path: rebuild the global value on every host, then
    # place local shards from it (works for any device/process interleaving)
    m_max = int(exts.max())
    stacked = np.asarray(multihost_utils.process_allgather(_pad_rows(local, m_max)))
    blocks = [np.take(stacked[q], np.arange(int(exts[q])), axis=axis) for q in range(nproc)]
    full = np.concatenate(blocks, axis=axis)
    widths = [(0, padded_total - total) if d == axis else (0, 0) for d in range(full.ndim)]
    padded = np.pad(full, widths)
    arr = jax.make_array_from_callback(padded.shape, sharding, lambda idx: padded[idx])
    return DNDarray(arr, gshape, dtype, axis, device, comm)


def asarray(obj, dtype=None, copy=None, order="C", is_split=None, device=None) -> DNDarray:
    """Convert to DNDarray without copying when possible (factories.py:483)."""
    return array(obj, dtype=dtype, copy=copy, order=order, is_split=is_split, device=device)


def __factory(shape, dtype, split, fill, device, comm, order="C") -> DNDarray:
    """Generic shape-based factory (factories.py:719)."""
    shape = sanitize_shape(shape)
    dtype = types.canonical_heat_type(types.float32 if dtype is None else dtype)
    split = sanitize_axis(shape, split)
    comm = sanitize_comm(comm)
    device = sanitize_device(device)
    # build directly at padded shape: no host materialization of the full array
    if split is None:
        padded_shape = shape
    else:
        padded_shape = tuple(
            comm.padded_extent(s) if d == split else s for d, s in enumerate(shape)
        )
    sharding = comm.sharding(split)
    arr = jax.jit(
        lambda: jnp.full(padded_shape, fill, dtype=dtype.jax_type()),
        out_shardings=sharding,
    )()
    return DNDarray(arr, shape, dtype, split, device, comm)


def __factory_like(a, dtype, split, factory, device, comm, order="C", **kwargs) -> DNDarray:
    """Mirror shape/dtype/split of ``a`` (factories.py:798)."""
    if isinstance(a, DNDarray):
        shape = a.shape
        dtype = dtype if dtype is not None else a.dtype
        split = split if split is not None else a.split
        device = device if device is not None else a.device
        comm = comm if comm is not None else a.comm
    else:
        shape = np.shape(a)
        dtype = dtype if dtype is not None else types.heat_type_of(a)
    return factory(shape, dtype=dtype, split=split, device=device, comm=comm, **kwargs)


def empty(shape, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    """Uninitialized array (factories.py:542) — zero-filled here (XLA has no
    uninitialized allocation)."""
    return __factory(shape, dtype, split, 0, device, comm, order)


def empty_like(a, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    return __factory_like(a, dtype, split, empty, device, comm, order)


def eye(shape, dtype=None, split=None, device=None, comm=None) -> DNDarray:
    """2-D identity-like array (factories.py:640)."""
    if isinstance(shape, (int, np.integer)):
        n, m = int(shape), int(shape)
    else:
        shape = sanitize_shape(shape)
        if len(shape) == 1:
            n = m = shape[0]
        else:
            n, m = shape[0], shape[1]
    dtype = types.canonical_heat_type(types.float32 if dtype is None else dtype)
    data = jnp.eye(n, m, dtype=dtype.jax_type())
    return DNDarray.from_dense(data, sanitize_axis((n, m), split), sanitize_device(device), sanitize_comm(comm))


def full(shape, fill_value, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    """Constant-filled array (factories.py:1022)."""
    if dtype is None:
        dtype = types.heat_type_of(fill_value)
    return __factory(shape, dtype, split, fill_value, device, comm, order)


def full_like(a, fill_value, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    return __factory_like(a, dtype, split, full, device, comm, order, fill_value=fill_value)


def linspace(
    start,
    stop,
    num: int = 50,
    endpoint: bool = True,
    retstep: bool = False,
    dtype=None,
    split=None,
    device=None,
    comm=None,
):
    """Evenly spaced samples over [start, stop] (factories.py:1105)."""
    num = int(num)
    if num <= 0:
        raise ValueError(f"number of samples 'num' must be non-negative, got {num}")
    data = jnp.linspace(float(start), float(stop), num, endpoint=endpoint)
    if dtype is not None:
        data = data.astype(types.canonical_heat_type(dtype).jax_type())
    else:
        data = data.astype(jnp.float32)
    ht = DNDarray.from_dense(data, sanitize_axis(data.shape, split), sanitize_device(device), sanitize_comm(comm))
    if retstep:
        if endpoint and num == 1:
            step = float("nan")  # numpy semantics for a single sample
        else:
            step = (float(stop) - float(start)) / (num - 1 if endpoint else num)
        return ht, step
    return ht


def logspace(start, stop, num=50, endpoint=True, base=10.0, dtype=None, split=None, device=None, comm=None) -> DNDarray:
    """Log-spaced samples (factories.py:1189)."""
    y = linspace(start, stop, num=num, endpoint=endpoint, split=split, device=device, comm=comm)
    from . import exponential

    result = exponential.pow_scalar_base(base, y)
    if dtype is not None:
        return result.astype(dtype)
    return result


def geomspace(start, stop, num=50, endpoint=True, dtype=None, split=None, device=None, comm=None) -> DNDarray:
    """Geometrically spaced samples (NumPy extension beyond the reference's
    factory set; numbers spaced so that each is a constant multiple of the
    previous, like np.geomspace)."""
    import math

    if start == 0 or stop == 0:
        raise ValueError("geometric sequence cannot include zero")
    sign = -1.0 if start < 0 else 1.0
    if (start < 0) != (stop < 0):
        raise ValueError("start and stop must have the same sign")
    y = logspace(
        math.log10(abs(start)),
        math.log10(abs(stop)),
        num=num,
        endpoint=endpoint,
        split=split,
        device=device,
        comm=comm,
    )
    result = y if sign > 0 else -y
    if dtype is not None:
        return result.astype(dtype)
    return result


def identity(n: int, dtype=None, split=None, device=None, comm=None) -> DNDarray:
    """The n x n identity matrix (NumPy parity wrapper over :func:`eye`)."""
    return eye(int(n), dtype=dtype, split=split, device=device, comm=comm)


def meshgrid(*arrays, indexing: str = "xy") -> List[DNDarray]:
    """Coordinate matrices from coordinate vectors (factories.py:1252).

    As in the reference, the last (xy) / second (ij) grid dimension is split
    if any input was split.
    """
    if indexing not in ("xy", "ij"):
        raise ValueError(f"indexing must be 'xy' or 'ij', got {indexing!r}")
    if not arrays:
        return []
    inputs = [array(a) for a in arrays]
    split_sources = [a for a in inputs if isinstance(a, DNDarray) and a.split is not None]
    comm = inputs[0].comm
    device = inputs[0].device
    dense = [a._dense() if isinstance(a, DNDarray) else jnp.asarray(a) for a in inputs]
    grids = jnp.meshgrid(*dense, indexing=indexing)
    if split_sources:
        out_split = 1 if indexing == "xy" else 0
        if len(grids[0].shape) <= out_split:
            out_split = 0
    else:
        out_split = None
    return [DNDarray.from_dense(g, out_split, device, comm) for g in grids]


def ones(shape, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    """One-filled array (factories.py:1380)."""
    return __factory(shape, dtype, split, 1, device, comm, order)


def ones_like(a, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    return __factory_like(a, dtype, split, ones, device, comm, order)


def zeros(shape, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    """Zero-filled array (factories.py:1431)."""
    return __factory(shape, dtype, split, 0, device, comm, order)


def zeros_like(a, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    return __factory_like(a, dtype, split, zeros, device, comm, order)


def from_partitioned(x, comm=None) -> DNDarray:
    """Build a DNDarray from an object exposing ``__partitioned__``
    (factories.py:849)."""
    parts = x.__partitioned__
    return from_partition_dict(parts, comm=comm)


def from_partition_dict(parts: dict, comm=None) -> DNDarray:
    """Build a DNDarray from a partition dict (factories.py:997)."""
    comm = sanitize_comm(comm)
    shape = tuple(parts["shape"])
    tiling = tuple(parts.get("partition_tiling", (1,) * len(shape)))
    split_candidates = [i for i, t in enumerate(tiling) if t > 1]
    split = split_candidates[0] if split_candidates else None
    keys = sorted(parts["partitions"].keys())
    pieces = []
    getter = parts.get("get")
    for k in keys:
        p = parts["partitions"][k]
        data = p["data"]
        if callable(data):
            data = data()
        elif data is not None and callable(getter):
            data = getter(data)
        if data is None:
            raise ValueError(f"partition {k} carries no data handle")
        piece = np.asarray(data)
        if piece.size == 0:
            continue
        pieces.append(piece)
    if split is None:
        global_np = pieces[0]
    else:
        global_np = np.concatenate(pieces, axis=split)
    return array(global_np, split=split, comm=comm)


def fromfunction(function, shape, dtype=None, split=None, device=None, comm=None) -> DNDarray:
    """Build an array by calling ``function`` over index grids (np parity)."""
    grids = jnp.meshgrid(*[jnp.arange(s) for s in shape], indexing="ij") if shape else []
    data = function(*grids)
    data = jnp.asarray(data)
    if dtype is not None:
        data = data.astype(types.canonical_heat_type(dtype).jax_type())
    return DNDarray.from_dense(jnp.broadcast_to(data, tuple(shape)), sanitize_axis(tuple(shape), split), sanitize_device(device), sanitize_comm(comm))


def fromiter(iter, dtype, count: int = -1, split=None, device=None, comm=None) -> DNDarray:
    """Build a 1-D array from an iterable (np parity)."""
    arr = np.fromiter(iter, dtype=np.dtype(types.canonical_heat_type(dtype).jax_type()), count=count)
    return array(arr, dtype=dtype, split=split, device=device, comm=comm)


def frombuffer(buffer, dtype=types.float32, count: int = -1, offset: int = 0, split=None, device=None, comm=None) -> DNDarray:
    """Interpret a buffer as a 1-D array (np parity)."""
    arr = np.frombuffer(buffer, dtype=np.dtype(types.canonical_heat_type(dtype).jax_type()), count=count, offset=offset)
    return array(arr.copy(), dtype=dtype, split=split, device=device, comm=comm)


def fromstring(string: str, dtype=types.float32, count: int = -1, sep: str = " ", split=None, device=None, comm=None) -> DNDarray:
    """Parse a 1-D array from a text string (np parity, text mode only)."""
    if not sep:
        raise ValueError("binary-mode fromstring is not supported; use frombuffer")
    arr = np.fromstring(string, dtype=np.dtype(types.canonical_heat_type(dtype).jax_type()), count=count, sep=sep)
    return array(arr, dtype=dtype, split=split, device=device, comm=comm)
