"""DNDarray: a global distributed array backed by a sharded jax.Array.

Analog of the reference's heat/core/dndarray.py (class at dndarray.py:39,
ctor :64-88, properties :90-360).  The design inverts the reference's:

* reference: every MPI process holds ONE local ``torch.Tensor`` chunk plus
  global metadata; all cross-chunk logic is explicit message passing.
* here: the wrapper holds ONE GLOBAL :class:`jax.Array` carrying a
  :class:`~jax.sharding.NamedSharding` over the communication mesh; ops are
  ``jnp`` calls and XLA/GSPMD materializes the communication.

Pad-and-mask invariant (SURVEY.md §7, decision 1)
-------------------------------------------------
XLA wants equal shards; heat's ``chunk()`` hands out ragged remainders.  The
stored global array (``self.__array``) is the true array padded *at the end*
of the split axis up to a multiple of ``comm.size``.  ``self.__gshape`` is
the TRUE global shape.  Pad contents are ARBITRARY: any op that reduces or
contracts across the split axis must first mask the padding with its own
neutral element (:meth:`_masked`); element-wise ops can ignore it.  For
divisible extents there is no padding and no cost.

The canonical distribution is the COMPUTE substrate — every op runs on the
padded canonical buffer and is layout-oblivious under GSPMD.  An arbitrary
ragged layout from ``redistribute_`` (dndarray.py:1216) is honored as a
metadata layer on top of it: ``lshape_map``/``counts_displs``/
``__partitioned__`` report the target map, ``balanced``/``is_balanced``
turn False while one is active, and the physically-placed ragged buffer is
materialized lazily (``_ragged_layout``).  ``balance_`` drops the layer —
no data ever needs to move back because the canonical backing never moved.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.comm import Communication, get_comm, sanitize_comm
from . import dispatch as _dispatch
from . import types
from .devices import Device, get_device, sanitize_device
from .stride_tricks import sanitize_axis

__all__ = ["DNDarray"]

Scalar = Union[int, float, bool, complex]


class LocalIndex:
    """Indexing proxy mirroring ``DNDarray.lloc`` semantics (dndarray.py:244)."""

    def __init__(self, arr: "DNDarray"):
        self.__arr = arr

    def __getitem__(self, key):
        return self.__arr.larray[key]

    def __setitem__(self, key, value):
        local = self.__arr.larray.at[key].set(jnp.asarray(value, self.__arr.larray.dtype))
        self.__arr._replace_local(local)


class DNDarray:
    """Distributed N-dimensional array (dndarray.py:39).

    Parameters mirror the reference ctor (dndarray.py:64-88) except that
    ``array`` is the *padded global* jax.Array rather than a process-local
    torch tensor.
    """

    def __init__(
        self,
        array: Optional[jax.Array],
        gshape: Tuple[int, ...],
        dtype,
        split: Optional[int],
        device: Device,
        comm: Communication,
        balanced: Optional[bool] = True,
        planar: Optional[Tuple[jax.Array, jax.Array]] = None,
        pending: Optional["_dispatch.PendingExpr"] = None,
    ):
        if array is None and planar is None and pending is None:
            raise ValueError(
                "DNDarray needs a backing array, planar planes, or a pending expression"
            )
        self.__array = array
        self.__planar = planar
        self.__pending = pending
        # the buffer a deferred in-place store (``_defer_store``) will be
        # written into; set only beside the pending chain that is its value
        self.__deferred: Optional[jax.Array] = None
        self.__gshape = tuple(int(s) for s in gshape)
        self.__dtype = types.canonical_heat_type(dtype)
        self.__split = split
        self.__device = device
        self.__comm = comm
        self.__balanced = True
        # active ragged layout from redistribute_: (true-lshape map, padded
        # per-device buffer) — None means the canonical distribution
        self.__target_map: Optional[np.ndarray] = None
        self.__ragged_buffer: Optional[jax.Array] = None

    # ------------------------------------------------------------------
    # construction helpers
    # ------------------------------------------------------------------
    @staticmethod
    def from_dense(
        arr: jax.Array,
        split: Optional[int],
        device: Optional[Device] = None,
        comm: Optional[Communication] = None,
    ) -> "DNDarray":
        """Wrap a true-shape global array: pad along ``split`` and place with
        the canonical sharding."""
        comm = sanitize_comm(comm)
        device = sanitize_device(device)
        gshape = tuple(int(s) for s in arr.shape)
        split = sanitize_axis(gshape, split)
        padded = _pad_to_canonical(arr, gshape, split, comm)
        return DNDarray(padded, gshape, types.canonical_heat_type(arr.dtype), split, device, comm)

    @staticmethod
    def from_planar(
        re: jax.Array,
        im: jax.Array,
        gshape: Tuple[int, ...],
        split: Optional[int],
        device: Optional[Device] = None,
        comm: Optional[Communication] = None,
    ) -> "DNDarray":
        """Wrap a complex array stored as two PADDED real planes (re, im).

        The planes live on the device mesh with canonical sharding; ops
        that understand planes (fft, complex_math) compute on them
        directly as real math, anything else transparently materializes
        the complex array on the mesh through :attr:`larray_padded`.
        This is the storage of the planar FFT engine (``HEAT_TPU_PLANAR``,
        fft/_planar.py), whose transforms are real matmuls end to end."""
        comm = sanitize_comm(comm)
        device = sanitize_device(device)
        if re.shape != im.shape:
            raise ValueError(f"planes disagree: {re.shape} vs {im.shape}")
        ctype = types.canonical_heat_type(
            jnp.complex128 if re.dtype == jnp.float64 else jnp.complex64
        )
        return DNDarray(None, gshape, ctype, split, device, comm, planar=(re, im))

    @staticmethod
    def from_pending(
        expr: "_dispatch.PendingExpr",
        gshape: Tuple[int, ...],
        split: Optional[int],
        device: Optional[Device] = None,
        comm: Optional[Communication] = None,
    ) -> "DNDarray":
        """Wrap a pending elementwise chain (core/dispatch.py).

        The expression's abstract shape is the PADDED layout; ``gshape``
        is the true global shape.  Materialization is deferred until the
        first :attr:`larray_padded` access — a reduction, collective,
        indexing, print, or host read — at which point the whole chain
        compiles as one fused executable through the dispatch cache."""
        return DNDarray(
            None, gshape, types.canonical_heat_type(expr.dtype), split,
            sanitize_device(device), sanitize_comm(comm), pending=expr,
        )

    @property
    def _planar(self) -> Optional[Tuple[jax.Array, jax.Array]]:
        """The (re, im) planes backing a planar complex array, if any."""
        return self.__planar

    @property
    def _pending(self) -> Optional["_dispatch.PendingExpr"]:
        """The deferred elementwise chain backing this array, if any."""
        return self.__pending

    @property
    def _fusion_source(self):
        """What a downstream fused program should consume: the pending
        chain when one is attached, else the concrete padded buffer."""
        if self.__pending is not None:
            return self.__pending
        return self.larray_padded

    def _donation_source(self) -> Optional[jax.Array]:
        """The concrete padded backing buffer for donation accounting
        (None when planar- or pending-backed: nothing donatable).  Pass
        the result straight into the donating call — binding it to an
        extra local would defeat the refcount proof.

        The caller's store replaces this array's value, so a deferred
        store need not run on its own: the array falls back on its
        buffer and lets the chain go (where the new store's source was
        built on this array the chain is part of it, and runs there)."""
        if self.__deferred is not None:
            self._replace(self.__deferred)
        return self.__array

    def _defer_store(self, result: "DNDarray", jdt) -> bool:
        """Take ``result``'s pending chain as this array's value in place
        of running the in-place store now (``_iop``); False where
        :func:`dispatch.defer_store` says that it has to run.  The buffer
        stays with the array as the deferred store's target, which
        :attr:`larray_padded` runs at the first read."""
        chain = _dispatch.defer_store(
            self.__array if self.__pending is None else self.__deferred, result._pending, jdt
        )
        if chain is None:
            return False
        if self.__pending is None:
            self.__deferred = self.__array
        self.__array = None
        self.__pending = chain
        self.__ragged_buffer = None
        return True

    def __materialize_planar(self) -> jax.Array:
        re, im = self.__planar
        ctype = self.__dtype.jax_type()
        comp = jax.lax.complex(re, im)  # on-device, sharding preserved
        return comp if comp.dtype == ctype else comp.astype(ctype)

    def _replace(self, padded: jax.Array) -> None:
        """Swap the backing padded array (same shape/dtype/metadata).

        Mutating VALUES keeps an active ragged layout — ``out=`` and
        in-place ops preserve the target's distribution like the
        reference — and only invalidates the lazily placed buffer."""
        self.__array = padded
        self.__planar = None
        self.__pending = None
        self.__deferred = None
        self.__ragged_buffer = None

    def _replace_local(self, local: jax.Array) -> None:
        """Replace this process's local chunk (single-process: everything).

        Multi-host: every process calls this collectively with its own block
        (the true rows of its devices' canonical shards); the global array is
        reassembled host-locally via
        ``jax.make_array_from_process_local_data`` — no communication, the
        analog of the reference's in-place ``_DNDarray__array`` swap.
        """
        padded_gshape = self._padded_shape  # planar-safe (read before nulling)
        self.__planar = None
        self.__pending = None
        self.__deferred = None
        self.__target_map = None
        self.__ragged_buffer = None
        if jax.process_count() == 1:
            new = DNDarray.from_dense(local, self.__split, self.__device, self.__comm)
            self.__array = new.larray_padded
            return
        comm = self.__comm
        split = self.__split
        if not comm.process_blocks_contiguous:
            raise NotImplementedError(
                "local replacement on an interleaved sub-mesh: use global __setitem__"
            )
        sharding = comm.sharding(split)
        if split is None:
            # replicated: each process supplies the full array
            self.__array = jax.make_array_from_process_local_data(
                sharding, np.asarray(local), self.__gshape
            )
            return
        _, lshape, _ = comm.process_chunk(self.__gshape, split)
        if tuple(int(s) for s in local.shape) != tuple(lshape):
            raise ValueError(
                f"local block must have shape {tuple(lshape)} on process "
                f"{comm.rank}, got {tuple(local.shape)}"
            )
        per = padded_gshape[split] // comm.size
        want = per * len(comm.local_participants)
        pad = want - lshape[split]
        if pad:
            widths = [(0, pad) if d == split else (0, 0) for d in range(self.ndim)]
            local = np.pad(np.asarray(local), widths)
        self.__array = jax.make_array_from_process_local_data(
            sharding, np.asarray(local), padded_gshape
        )

    # ------------------------------------------------------------------
    # padded / dense / masked views
    # ------------------------------------------------------------------
    @property
    def larray_padded(self) -> jax.Array:
        """The stored padded global jax.Array.  This is THE fusion
        boundary: a pending elementwise chain compiles and runs here as
        one cached executable (reductions, collectives, indexing,
        printing, and host reads all funnel through this property);
        planar planes materialize here too.  A deferred in-place store
        runs here as that one program, through the donating store: its
        output aliases the array's old buffer where that is unshared."""
        if self.__array is None:
            if self.__pending is not None:
                sharding = self.__comm.sharding(self.__split)
                if self.__deferred is not None:
                    self.__array = _dispatch.cast_store(
                        self.__deferred, self.__pending, self.__dtype.jax_type(), sharding
                    )
                    self.__deferred = None
                else:
                    self.__array = _dispatch.materialize(self.__pending, sharding)
                self.__pending = None
            else:
                self.__array = self.__materialize_planar()
        return self.__array

    @property
    def _padded_shape(self) -> Tuple[int, ...]:
        """Shape of the padded buffer without materializing planar planes
        or pending chains."""
        if self.__array is not None:
            buf = self.__array
        elif self.__pending is not None:
            return tuple(int(s) for s in self.__pending.shape)
        else:
            buf = self.__planar[0]
        return tuple(int(s) for s in buf.shape)

    @property
    def _padded_dtype(self):
        """dtype of the padded buffer without materializing pending
        chains (planar arrays materialize: their composed dtype is the
        storage dtype)."""
        if self.__array is not None:
            return self.__array.dtype
        if self.__pending is not None:
            return self.__pending.dtype
        return self.larray_padded.dtype

    @property
    def _pad(self) -> int:
        """Number of padding rows along the split axis (0 if divisible)."""
        if self.__split is None:
            return 0
        return self._padded_shape[self.__split] - self.__gshape[self.__split]

    def _dense(self) -> jax.Array:
        """The true-shape global array (slices off padding if any)."""
        if self._pad == 0:
            return self.larray_padded
        sl = tuple(
            slice(0, self.__gshape[d]) if d == self.__split else slice(None)
            for d in range(self.ndim)
        )
        return self.larray_padded[sl]

    def _masked(self, neutral: Scalar) -> jax.Array:
        """Padded array with padding overwritten by ``neutral`` — safe to
        reduce/contract across the split axis."""
        buf = self.larray_padded
        if self._pad == 0:
            return buf
        s = self.__split
        idx = jax.lax.broadcasted_iota(jnp.int32, buf.shape, s)
        return jnp.where(idx < self.__gshape[s], buf, jnp.asarray(neutral, buf.dtype))

    # ------------------------------------------------------------------
    # properties (dndarray.py:90-360)
    # ------------------------------------------------------------------
    @property
    def balanced(self) -> bool:
        return self.__target_map is None

    @property
    def comm(self) -> Communication:
        return self.__comm

    @comm.setter
    def comm(self, comm: Communication):
        self.__comm = sanitize_comm(comm)

    @property
    def device(self) -> Device:
        return self.__device

    @property
    def dtype(self):
        return self.__dtype

    @property
    def gshape(self) -> Tuple[int, ...]:
        return self.__gshape

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.__gshape

    @property
    def ndim(self) -> int:
        return len(self.__gshape)

    @property
    def size(self) -> int:
        """Total number of (true) elements, dndarray.py:222."""
        return int(np.prod(self.__gshape, dtype=np.int64)) if self.__gshape else 1

    @property
    def gnumel(self) -> int:
        return self.size

    @property
    def gnbytes(self) -> int:
        return self.size * np.dtype(self.__dtype.jax_type()).itemsize

    @property
    def nbytes(self) -> int:
        return self.gnbytes

    @property
    def itemsize(self) -> int:
        """Bytes per element (NumPy parity)."""
        return np.dtype(self.__dtype.jax_type()).itemsize

    @property
    def flat(self):
        """Flat iterator over the global array (np.ndarray.flat analog)."""
        return iter(self.numpy().ravel())

    @property
    def larray(self) -> jax.Array:
        """This process's local chunk of the TRUE array (dndarray.py:140).

        Single-controller: the full dense array. Multi-process: the block of
        rows this process's devices own (without padding).
        """
        if jax.process_count() == 1:
            return self._dense()
        # multi-host: assemble this process's block from its ADDRESSABLE
        # device shards — purely host-local, no collective (the analog of the
        # reference's per-rank torch tensor, dndarray.py:140)
        split = self.__split
        shards = self.larray_padded.addressable_shards
        if split is None:
            return jnp.asarray(shards[0].data)
        shards = sorted(shards, key=lambda s: s.index[split].start or 0)
        # shards sit on different local devices; assemble via host (numpy)
        blocks = [np.asarray(s.data) for s in shards]
        local_padded = blocks[0] if len(blocks) == 1 else np.concatenate(blocks, axis=split)
        _, lshape, _ = self.__comm.process_chunk(self.__gshape, split)
        sl = tuple(
            slice(0, lshape[split]) if d == split else slice(None) for d in range(self.ndim)
        )
        return jnp.asarray(local_padded[sl])

    @property
    def lshape(self) -> Tuple[int, ...]:
        if jax.process_count() > 1:
            # pure metadata — larray would materialize the local block
            return tuple(int(s) for s in self.__comm.process_chunk(self.__gshape, self.__split)[1])
        return tuple(int(s) for s in self.larray.shape)

    @property
    def lnumel(self) -> int:
        return int(np.prod(self.lshape, dtype=np.int64)) if self.lshape else 1

    @property
    def lnbytes(self) -> int:
        return self.lnumel * np.dtype(self.__dtype.jax_type()).itemsize

    @property
    def lshape_map(self) -> np.ndarray:
        """(comm.size, ndim) true local shapes per participant
        (dndarray.py:304) — pure metadata, no communication.  Reflects an
        active ragged ``redistribute_`` target."""
        if self.__target_map is not None:
            return self.__target_map.copy()
        return self.__comm.lshape_map(self.__gshape, self.__split)

    @property
    def lloc(self) -> LocalIndex:
        return LocalIndex(self)

    @property
    def split(self) -> Optional[int]:
        return self.__split

    @property
    def stride(self) -> Tuple[int, ...]:
        """Element strides of the dense array (row-major; dndarray.py:331)."""
        st = []
        acc = 1
        for s in reversed(self.__gshape):
            st.append(acc)
            acc *= s
        return tuple(reversed(st))

    @property
    def strides(self) -> Tuple[int, ...]:
        itemsize = np.dtype(self.__dtype.jax_type()).itemsize
        return tuple(s * itemsize for s in self.stride)

    @property
    def imag(self) -> "DNDarray":
        from . import complex_math

        return complex_math.imag(self)

    @property
    def real(self) -> "DNDarray":
        from . import complex_math

        return complex_math.real(self)

    @property
    def T(self) -> "DNDarray":
        from .linalg import basics

        return basics.transpose(self)

    @property
    def __partitioned__(self) -> dict:
        """Partition-interface interop protocol (dndarray.py:189-204)."""
        return self.create_partition_interface()

    # ------------------------------------------------------------------
    # conversion / export (dndarray.py:476-785, 1094-1214)
    # ------------------------------------------------------------------
    def astype(self, dtype, copy: bool = True) -> "DNDarray":
        """Cast to ``dtype`` (dndarray.py:482)."""
        dtype = types.canonical_heat_type(dtype)
        casted = self.larray_padded.astype(dtype.jax_type())
        out = DNDarray(casted, self.__gshape, dtype, self.__split, self.__device, self.__comm)
        if not copy:
            self.__array = casted
            self.__planar = None
            self.__pending = None
            self.__ragged_buffer = None  # values changed: re-place lazily
            self.__dtype = dtype
            return self
        return out

    def numpy(self) -> np.ndarray:
        """Gather the full array to host numpy (dndarray.py:1177).

        Multi-host: collective — every process receives the full value (the
        reference's resplit-to-None + local numpy, dndarray.py:1177-1192).
        """
        dense = self._dense()
        if jax.process_count() > 1 and not dense.is_fully_addressable:
            from jax.experimental import multihost_utils

            return np.asarray(multihost_utils.process_allgather(dense, tiled=True))
        return np.asarray(dense)

    def __array__(self, dtype=None) -> np.ndarray:
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def tolist(self) -> list:
        return self.numpy().tolist()

    def item(self):
        """Scalar value of a single-element array (dndarray.py:1152)."""
        if self.size != 1:
            raise ValueError(f"only one-element arrays can be converted to Python scalars, got shape {self.__gshape}")
        if jax.process_count() > 1:  # collective fetch
            return self.numpy().reshape(()).item()
        return np.asarray(self._dense().reshape(())).item()

    def cpu(self) -> "DNDarray":
        """Kept for API parity (dndarray.py:646); placement is mesh-owned."""
        return self

    def create_partition_interface(self) -> dict:
        """``__partitioned__`` dict (dndarray.py:688-785): shapes/starts/
        location per partition for Dask/Arkouda-style interop."""
        lmap = self.lshape_map  # ragged-aware
        starts = np.zeros_like(lmap)
        if self.__split is not None:
            starts[1:, self.__split] = np.cumsum(lmap[:-1, self.__split])
        partitions = {}
        for r in range(self.__comm.size):
            slices = tuple(
                slice(int(starts[r, d]), int(starts[r, d] + lmap[r, d]))
                if d == self.__split
                else slice(0, s)
                for d, s in enumerate(self.__gshape)
            )

            def _get(slices=slices):
                return np.asarray(self._dense()[slices])

            partitions[(r,) + (0,) * max(self.ndim - 1, 0)] = {
                "start": tuple(int(x) for x in starts[r]),
                "shape": tuple(int(x) for x in lmap[r]),
                "data": _get,
                "location": [r],
                "dtype": np.dtype(self.__dtype.jax_type()),
            }
        grid = [1] * max(self.ndim, 1)
        if self.__split is not None:
            grid[self.__split] = self.__comm.size
        return {
            "shape": self.__gshape,
            "partition_tiling": tuple(grid),
            "partitions": partitions,
            "locals": [(self.__comm.rank,) + (0,) * max(self.ndim - 1, 0)],
            "get": lambda h: h() if callable(h) else h,
        }

    # ------------------------------------------------------------------
    # distribution management
    # ------------------------------------------------------------------
    def is_balanced(self, force_check: bool = False) -> bool:
        """False only while a ragged ``redistribute_`` target is active
        (dndarray.py:1155); the compute substrate is always canonical."""
        return self.__target_map is None

    def is_distributed(self) -> bool:
        """Whether data lives on more than one participant (dndarray.py:1166)."""
        return self.__split is not None and self.__comm.size > 1

    def counts_displs(self) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """(counts, displacements) along the split axis per participant
        (dndarray.py:~630): pure sharding metadata (ragged-aware)."""
        if self.__split is None:
            raise ValueError("Non-distributed DNDarray has no counts and displacements")
        if self.__target_map is not None:
            counts = tuple(int(c) for c in self.__target_map[:, self.__split])
            displs = tuple(int(d) for d in np.cumsum((0,) + counts[:-1]))
            return counts, displs
        counts, displs, _ = self.__comm.counts_displs_shape(self.__gshape, self.__split)
        return tuple(int(c) for c in counts), tuple(int(d) for d in displs)

    def create_lshape_map(self, force_check: bool = False) -> np.ndarray:
        """Recompute the (size, ndim) local-shape map (dndarray.py:~660).

        Metadata-only here: the canonical distribution is fully determined by
        (gshape, split, comm), so no communication happens."""
        return self.lshape_map

    def balance_(self) -> "DNDarray":
        """Return to the canonical (balanced) distribution (dndarray.py:509):
        drops any ragged ``redistribute_`` layout; the canonical backing
        never moved, so no data shuffles."""
        self.__target_map = None
        self.__ragged_buffer = None
        return self

    def resplit_(self, axis: Optional[int] = None) -> "DNDarray":
        """In-place re-split along a new axis (dndarray.py:1415-1501).

        split->None is the reference's Allgatherv; None->split its local
        slice; split->split its one-shot Alltoallw — all three are a single
        ``device_put`` with the new NamedSharding here (XLA emits the
        all-gather / slice / all-to-all over ICI).
        """
        axis = sanitize_axis(self.__gshape, axis)
        if axis == self.__split:
            return self
        if self.__planar is not None:
            # planar-backed: materialize, then place; no donation
            dense = self._dense()
            padded = _pad_to_canonical(dense, self.__gshape, axis, self.__comm)
        else:
            # one cached executable: slice old padding + pad new split +
            # reshard, donating the dead backing buffer when unshared
            old_slice = (
                (self.__split, self.__gshape[self.__split]) if self._pad > 0 else None
            )
            pad_widths = None
            if axis is not None:
                pad = self.__comm.pad_amount(self.__gshape[axis])
                if pad:
                    pad_widths = tuple(
                        (0, pad if d == axis else 0) for d in range(self.ndim)
                    )
            padded = _dispatch.repad(
                self.larray_padded, old_slice, pad_widths,
                self.__comm.sharding(axis), donate=True,
            )
        self.__array = padded
        self.__planar = None
        self.__pending = None
        self.__split = axis
        self.__target_map = None
        self.__ragged_buffer = None
        return self

    def reshard_(self, comm: Optional[Communication] = None) -> "DNDarray":
        """In-place re-materialization onto a different :class:`Communication`.

        The elastic-resume primitive (docs/elasticity.md): after
        ``comm.reshape(n)`` replaced the mesh, every live array must move
        to the survivors.  Keeps the global value and the split axis;
        recomputes the canonical padded distribution for the NEW world
        size (slice the old world's padding, pad for the new, place with
        the new canonical sharding).  Unlike ``resplit_`` — one donated
        executable within a mesh — the placement across meshes is a
        ``device_put`` copy: XLA cannot alias buffers across two device
        assignments, so the old backing is freed only when its last
        reference drops.  No-op when ``comm`` is this array's comm."""
        comm = sanitize_comm(comm)
        if comm is self.__comm or comm == self.__comm:
            return self
        split = self.__split
        if self.__planar is not None:
            re, im = self.__planar
            # planar planes carry the OLD world's padding: strip it
            # through the dense view, then re-pad per plane for the new
            pad = self._pad
            if pad:
                sl = tuple(
                    slice(0, self.__gshape[d]) if d == split else slice(None)
                    for d in range(self.ndim)
                )
                re, im = re[sl], im[sl]
            self.__planar = (
                _pad_to_canonical(re, self.__gshape, split, comm),
                _pad_to_canonical(im, self.__gshape, split, comm),
            )
            self.__array = None
        else:
            dense = self._dense()
            self.__array = _pad_to_canonical(dense, self.__gshape, split, comm)
            self.__planar = None
        self.__pending = None
        self.__target_map = None
        self.__ragged_buffer = None
        self.__comm = comm
        return self

    def resplit(self, axis: Optional[int] = None) -> "DNDarray":
        """Out-of-place resplit (manipulations.py:3633)."""
        axis = sanitize_axis(self.__gshape, axis)
        if axis == self.__split:
            return DNDarray(
                self.__array, self.__gshape, self.__dtype, self.__split,
                self.__device, self.__comm, planar=self.__planar,
                pending=self.__pending,
            )
        dense = self._dense()
        return DNDarray.from_dense(dense, axis, self.__device, self.__comm)

    @staticmethod
    def _as_host_int_map(m, name: str) -> np.ndarray:
        """Host int array from a DNDarray/torch/np map argument; TypeError
        for non-numeric inputs (reference dndarray.py:1256-1270)."""
        if isinstance(m, DNDarray):
            m = m.numpy()
        elif hasattr(m, "detach"):  # torch tensor
            m = m.detach().cpu().numpy()
        arr = np.asarray(m)
        if not np.issubdtype(arr.dtype, np.number):
            raise TypeError(f"{name} must be an integer array, got {arr.dtype}")
        return arr.astype(np.int64)

    def redistribute_(self, lshape_map=None, target_map=None) -> "DNDarray":
        """Shuffle chunks to match an arbitrary ``target_map``
        (dndarray.py:1216-1366).

        The reference issues per-rank sends until every rank holds its
        target rows.  Here the canonical padded buffer stays the compute
        substrate (every op is layout-oblivious under GSPMD), and the
        ragged target becomes (a) a metadata layer that ``lshape_map`` /
        ``counts_displs`` / ``__partitioned__`` report and (b) a physical
        per-device buffer — one global gather whose index plan follows
        the target cumsum, so XLA emits a single all-to-all placing each
        device's target rows in its shard (slots padded to the largest
        target chunk: the pad-and-mask policy applied to a ragged map).
        Only the split column of ``target_map`` is consulted, like the
        reference."""
        if lshape_map is not None:
            lm = self._as_host_int_map(lshape_map, "lshape_map")
            if lm.shape != (self.__comm.size, max(self.ndim, 1)):
                raise ValueError(
                    f"lshape_map must have shape ({self.__comm.size}, {self.ndim}), "
                    f"got {lm.shape}"
                )
        if target_map is None:
            # no target = balance (the reference's no-target redistribute_
            # normalizes to the balanced layout): drop any ragged layer
            self.__target_map = None
            self.__ragged_buffer = None
            return self
        tm = self._as_host_int_map(target_map, "target_map")
        if tm.shape != (self.__comm.size, max(self.ndim, 1)):
            raise ValueError(
                f"target_map must have shape ({self.__comm.size}, {self.ndim}), "
                f"got {tm.shape}"
            )
        if self.__split is None:
            return self  # nothing to redistribute (reference does nothing)
        extent = self.__gshape[self.__split]
        counts = tm[:, self.__split]
        if (counts < 0).any() or int(counts.sum()) != extent:
            raise ValueError(
                f"target_map must distribute all {extent} rows of axis "
                f"{self.__split}, got counts {counts.tolist()}"
            )
        canonical = self.__comm.lshape_map(self.__gshape, self.__split)
        if (counts == canonical[:, self.__split]).all():
            self.__target_map = None
            self.__ragged_buffer = None
            return self
        full = np.tile(np.asarray(self.__gshape, np.int64), (self.__comm.size, 1))
        full[:, self.__split] = counts
        self.__target_map = full
        self.__ragged_buffer = None  # placed lazily: no consumer, no cost
        return self

    @property
    def _active_target_map(self) -> Optional[np.ndarray]:
        """The ragged ``redistribute_`` target map, or None when canonical
        (internal; see ``_propagate_layout_from``)."""
        return self.__target_map

    def _propagate_layout_from(self, *sources) -> "DNDarray":
        """Adopt the first compatible active ragged layout among ``sources``.

        Reference semantics: op results keep the (lhs-first) operand's
        distribution (heat/core/sanitation.py:32-158).  Because the compute
        substrate here is always canonical, propagation is metadata-only —
        the result's ``lshape_map``/``counts_displs``/``__partitioned__``
        report the adopted map and the physical ragged buffer is placed
        lazily on first ``_ragged_layout`` access.  A source is compatible
        when it shares this result's global shape and split; reductions and
        shape-changing ops therefore return balanced arrays (documented in
        docs/design.md).  Planar (complex real-pair) results never adopt a
        layout: ``_ragged_layout`` would have to materialize the complex
        value, which the planar chain exists to avoid."""
        if self.__planar is not None:
            return self
        for src in sources:
            if not isinstance(src, DNDarray):
                continue
            if self.__split != src.split or self.__gshape != src.shape:
                continue
            # first compatible operand decides: its balanced layout wins
            # too (the reference redistributes t2 to t1's map)
            tm = src._active_target_map
            if tm is not None:
                self.__target_map = tm.copy()
                self.__ragged_buffer = None
            return self
        return self

    @property
    def _ragged_layout(self):
        """(target lshape map, padded per-device buffer) when a ragged
        ``redistribute_`` is active, else None.  The buffer — each device
        holding its target rows, slots padded to the largest chunk — is
        built on first access: one global gather whose index plan follows
        the target cumsum (XLA emits a single all-to-all), cached until
        the layout or the data changes."""
        if self.__target_map is None:
            return None
        if self.__ragged_buffer is None:
            counts = self.__target_map[:, self.__split]
            cum = np.concatenate([[0], np.cumsum(counts)])
            bmax = max(int(counts.max()), 1)
            plan = np.zeros((self.__comm.size, bmax), np.int64)
            for d in range(self.__comm.size):
                plan[d, : counts[d]] = cum[d] + np.arange(counts[d])
            ragged = jnp.take(
                self._dense(), jnp.asarray(plan.reshape(-1)), axis=self.__split
            )
            self.__ragged_buffer = jax.device_put(
                ragged, self.__comm.sharding(self.__split)
            )
        return self.__target_map, self.__ragged_buffer

    def collect_(self, target_rank: int = 0) -> "DNDarray":
        """Gather the full array onto every participant (dndarray.py:581's
        closest mesh analog: resplit to replicated)."""
        return self.resplit_(None)

    # ------------------------------------------------------------------
    # indexing — delegates to jnp advanced indexing on the dense view
    # (reference: dndarray.py:836-1093 __getitem__, :1503-1791 __setitem__)
    # ------------------------------------------------------------------
    def __getitem__(self, key) -> Union["DNDarray", Scalar]:
        key, out_split_hint = _convert_key(self, key)
        res = self._dense()[key]
        if res.ndim == 0:
            return DNDarray.from_dense(res, None, self.__device, self.__comm)
        out_split = out_split_hint if out_split_hint is None or out_split_hint < res.ndim else None
        return DNDarray.from_dense(res, out_split, self.__device, self.__comm)

    def __setitem__(self, key, value):
        key, _ = _convert_key(self, key)
        if isinstance(value, DNDarray):
            value = value._dense()
        value = jnp.asarray(value, dtype=self.__dtype.jax_type())
        key_p = self._padded_safe_key(key)
        if key_p is not None:
            # fast path: write straight into the padded buffer — no dense
            # slice + re-pad device round trip (one fused scatter on device)
            out = self.larray_padded.at[key_p].set(value)
            # scatter output sharding followed the value operand; restore
            # the canonical placement downstream shard_maps rely on
            want = self.__comm.sharding(self.__split, self.ndim)
            if not out.sharding.is_equivalent_to(want, out.ndim):
                out = jax.device_put(out, want)
            self.__array = out
            self.__planar = None
            self.__pending = None
            self.__ragged_buffer = None
            return
        new_dense = self._dense().at[key].set(value)
        self.__array = _pad_to_canonical(new_dense, self.__gshape, self.__split, self.__comm)
        self.__planar = None
        self.__pending = None
        self.__ragged_buffer = None

    def _padded_safe_key(self, key):
        """Return a key usable directly on the padded buffer, or None.

        Safe when there is no padding (dense view == padded buffer), or
        when the component addressing the split axis provably never
        touches the padding rows: an in-bounds integer or bounded slice,
        an integer index array (negative entries are remapped against the
        TRUE extent — canonical padding sits at the END of the axis, so
        non-negative global indices are identical in both buffers), or a
        1-D boolean mask (padded with False over the padding rows).
        Components on other axes are unconstrained (no padding there)."""
        keys = list(key) if isinstance(key, tuple) else [key]
        # bool scalars are advanced indexing (numpy adds an axis), not ints —
        # and bool is an int subclass, so screen them out before any int check
        if any(isinstance(k, (bool, np.bool_)) for k in keys):
            return None
        if self._pad == 0:
            return key
        split = self.__split
        extent = self.__gshape[split]

        def consumed(k) -> int:
            if k is None:
                return 0
            if isinstance(k, (jax.Array, np.ndarray)) and k.dtype == np.bool_:
                return int(k.ndim)
            return 1

        n_explicit = sum(consumed(k) for k in keys if k is not Ellipsis)
        dim = 0
        for i, k in enumerate(keys):
            if isinstance(k, (list, tuple)):
                keys[i] = k = np.asarray(k)
            if k is None:
                continue
            if k is Ellipsis:
                dim += self.ndim - n_explicit
                if dim > split:
                    return None  # padding exposed via the implicit full slice
                continue
            c = consumed(k)
            if dim <= split < dim + c:
                if isinstance(k, (int, np.integer)):
                    j = int(k) + (extent if k < 0 else 0)
                    if 0 <= j < extent:
                        keys[i] = j
                        return tuple(keys)
                    return None
                if isinstance(k, slice):
                    if k.step not in (None, 1):
                        return None
                    start, stop, _ = k.indices(extent)
                    if 0 <= start <= stop <= extent:
                        keys[i] = slice(start, stop)
                        return tuple(keys)
                    return None
                if isinstance(k, (jax.Array, np.ndarray)):
                    if k.dtype == np.bool_:
                        if k.ndim != 1 or k.shape[0] != extent:
                            return None  # multi-dim masks span other dims too
                        widths = [(0, self._pad)]
                        keys[i] = (
                            np.pad(k, widths) if isinstance(k, np.ndarray) else jnp.pad(k, widths)
                        )
                        return tuple(keys)
                    if jnp.issubdtype(k.dtype, jnp.integer):
                        mod = np if isinstance(k, np.ndarray) else jnp
                        keys[i] = mod.where(k < 0, k + extent, k)
                        return tuple(keys)
                return None
            dim += c
        return None  # split axis addressed implicitly (full slice over padding)

    def __len__(self) -> int:
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.__gshape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # ------------------------------------------------------------------
    # printing (printing.py:184)
    # ------------------------------------------------------------------
    def __repr__(self) -> str:
        override = getattr(type(self), "__repr_override__", None)
        if override is not None:  # installed via printing.set_string_function
            return override(self)
        from . import printing

        return printing.__str__(self)

    def __str__(self) -> str:
        override = getattr(type(self), "__str_override__", None)
        if override is not None:
            return override(self)
        return self.__repr__()

    # ------------------------------------------------------------------
    # operator overloads — bound to the ops layer via late imports, the
    # same late-binding trick heat uses (arithmetics.py operator sections)
    # ------------------------------------------------------------------
    def __add__(self, other):
        from . import arithmetics

        return arithmetics.add(self, other)

    __radd__ = __add__

    def __sub__(self, other):
        from . import arithmetics

        return arithmetics.sub(self, other)

    def __rsub__(self, other):
        from . import arithmetics

        return arithmetics.sub(other, self)

    def __mul__(self, other):
        from . import arithmetics

        return arithmetics.mul(self, other)

    __rmul__ = __mul__

    def __truediv__(self, other):
        from . import arithmetics

        return arithmetics.div(self, other)

    def __rtruediv__(self, other):
        from . import arithmetics

        return arithmetics.div(other, self)

    def __floordiv__(self, other):
        from . import arithmetics

        return arithmetics.floordiv(self, other)

    def __rfloordiv__(self, other):
        from . import arithmetics

        return arithmetics.floordiv(other, self)

    def __mod__(self, other):
        from . import arithmetics

        return arithmetics.mod(self, other)

    def __rmod__(self, other):
        from . import arithmetics

        return arithmetics.mod(other, self)

    def __divmod__(self, other):
        """numpy parity (beyond the reference's operator set):
        ``divmod(a, b) == (a // b, a % b)`` elementwise."""
        from . import arithmetics

        return arithmetics.divmod(self, other)

    def __rdivmod__(self, other):
        from . import arithmetics

        return arithmetics.divmod(other, self)

    def __contains__(self, item) -> bool:
        """numpy's membership semantics: ``x in a`` is ``(a == x).any()``,
        with non-comparable items reporting False like numpy (one
        collective reduce; beyond the reference's surface)."""
        from . import logical, relational

        try:
            return bool(logical.any(relational.eq(self, item)))
        except TypeError:
            return False

    def __pow__(self, other):
        from . import arithmetics

        return arithmetics.pow(self, other)

    def __rpow__(self, other):
        from . import arithmetics

        return arithmetics.pow(other, self)

    def __matmul__(self, other):
        from .linalg import basics

        type_name = type(other).__name__
        if type_name in ("DCSR_matrix", "DCSC_matrix", "DCSX_matrix"):
            # dense @ sparse routes through the sparse layer (Python will
            # not try __rmatmul__ once this raises, so dispatch here)
            from ..sparse import arithmetics as sparse_arithmetics

            return sparse_arithmetics.matmul(self, other)
        return basics.matmul(self, other)

    def __neg__(self):
        from . import arithmetics

        return arithmetics.neg(self)

    def __pos__(self):
        from . import arithmetics

        return arithmetics.pos(self)

    def __abs__(self):
        from . import rounding

        return rounding.abs(self)

    def __invert__(self):
        from . import arithmetics

        return arithmetics.invert(self)

    def __and__(self, other):
        from . import arithmetics

        return arithmetics.bitwise_and(self, other)

    __rand__ = __and__

    def __or__(self, other):
        from . import arithmetics

        return arithmetics.bitwise_or(self, other)

    __ror__ = __or__

    def __xor__(self, other):
        from . import arithmetics

        return arithmetics.bitwise_xor(self, other)

    __rxor__ = __xor__

    def __lshift__(self, other):
        from . import arithmetics

        return arithmetics.left_shift(self, other)

    def __rshift__(self, other):
        from . import arithmetics

        return arithmetics.right_shift(self, other)

    def __eq__(self, other):
        from . import relational

        return relational.eq(self, other)

    def __ne__(self, other):
        from . import relational

        return relational.ne(self, other)

    def __lt__(self, other):
        from . import relational

        return relational.lt(self, other)

    def __le__(self, other):
        from . import relational

        return relational.le(self, other)

    def __gt__(self, other):
        from . import relational

        return relational.gt(self, other)

    def __ge__(self, other):
        from . import relational

        return relational.ge(self, other)

    __hash__ = None

    def __bool__(self) -> bool:
        return bool(self.item())

    def __int__(self) -> int:
        return int(self.item())

    def __float__(self) -> float:
        return float(self.item())

    def __complex__(self) -> complex:
        return complex(self.item())

    # in-place arithmetic: replace backing array
    def __iadd__(self, other):
        return _iop(self, self.__add__(other))

    def __isub__(self, other):
        return _iop(self, self.__sub__(other))

    def __imul__(self, other):
        return _iop(self, self.__mul__(other))

    def __itruediv__(self, other):
        return _iop(self, self.__truediv__(other))

    def __ifloordiv__(self, other):
        return _iop(self, self.__floordiv__(other))

    def __imod__(self, other):
        return _iop(self, self.__mod__(other))

    def __ipow__(self, other):
        return _iop(self, self.__pow__(other))

    # ------------------------------------------------------------------
    # method shims into the ops layer (heat binds ~70 of these)
    # ------------------------------------------------------------------
    def abs(self, out=None, dtype=None):
        from . import rounding

        return rounding.abs(self, out, dtype)

    def all(self, axis=None, out=None, keepdims=False):
        from . import logical

        return logical.all(self, axis, out, keepdims)

    def any(self, axis=None, out=None, keepdims=False):
        from . import logical

        return logical.any(self, axis, out, keepdims)

    def argmax(self, axis=None, out=None, **kwargs):
        from . import statistics

        return statistics.argmax(self, axis, out, **kwargs)

    def argmin(self, axis=None, out=None, **kwargs):
        from . import statistics

        return statistics.argmin(self, axis, out, **kwargs)

    def ceil(self, out=None):
        from . import rounding

        return rounding.ceil(self, out)

    def clip(self, min=None, max=None, out=None):
        from . import rounding

        return rounding.clip(self, min, max, out)

    def copy(self) -> "DNDarray":
        from . import memory

        return memory.copy(self)

    def cumsum(self, axis, dtype=None, out=None):
        from . import arithmetics

        return arithmetics.cumsum(self, axis, dtype, out)

    def cumprod(self, axis, dtype=None, out=None):
        from . import arithmetics

        return arithmetics.cumprod(self, axis, dtype, out)

    def exp(self, out=None):
        from . import exponential

        return exponential.exp(self, out)

    def expand_dims(self, axis):
        from . import manipulations

        return manipulations.expand_dims(self, axis)

    def flatten(self):
        from . import manipulations

        return manipulations.flatten(self)

    def floor(self, out=None):
        from . import rounding

        return rounding.floor(self, out)

    def fill_diagonal(self, value) -> "DNDarray":
        n = min(self.__gshape[0], self.__gshape[-1]) if self.ndim >= 2 else 0
        if self.ndim != 2:
            raise ValueError("fill_diagonal requires a 2-D array")
        dense = self._dense()
        idx = jnp.arange(n)
        dense = dense.at[idx, idx].set(jnp.asarray(value, dense.dtype))
        self.__array = _pad_to_canonical(dense, self.__gshape, self.__split, self.__comm)
        self.__planar = None
        self.__pending = None
        self.__ragged_buffer = None
        return self

    def log(self, out=None):
        from . import exponential

        return exponential.log(self, out)

    def max(self, axis=None, out=None, keepdims=False):
        from . import statistics

        return statistics.max(self, axis, out, keepdims)

    def mean(self, axis=None, keepdims: bool = False):
        from . import statistics

        return statistics.mean(self, axis, keepdims=keepdims)

    def median(self, axis=None, keepdims=False):
        from . import statistics

        return statistics.median(self, axis, keepdims)

    def min(self, axis=None, out=None, keepdims=False):
        from . import statistics

        return statistics.min(self, axis, out, keepdims)

    def prod(self, axis=None, out=None, keepdims=False):
        from . import arithmetics

        return arithmetics.prod(self, axis, out, keepdims)

    def ravel(self):
        from . import manipulations

        return manipulations.ravel(self)

    def reshape(self, *shape, new_split=None):
        from . import manipulations

        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return manipulations.reshape(self, shape, new_split=new_split)

    def round(self, decimals=0, out=None, dtype=None):
        from . import rounding

        return rounding.round(self, decimals, out, dtype)

    def sin(self, out=None):
        from . import trigonometrics

        return trigonometrics.sin(self, out)

    def cos(self, out=None):
        from . import trigonometrics

        return trigonometrics.cos(self, out)

    def sqrt(self, out=None):
        from . import exponential

        return exponential.sqrt(self, out)

    def squeeze(self, axis=None):
        from . import manipulations

        return manipulations.squeeze(self, axis)

    def std(self, axis=None, ddof=0, **kwargs):
        from . import statistics

        return statistics.std(self, axis, ddof=ddof, **kwargs)

    def sum(self, axis=None, out=None, keepdims=False):
        from . import arithmetics

        return arithmetics.sum(self, axis, out, keepdims)

    def tan(self, out=None):
        from . import trigonometrics

        return trigonometrics.tan(self, out)

    def transpose(self, axes=None):
        from .linalg import basics

        return basics.transpose(self, axes)

    def tril(self, k=0):
        from .linalg import basics

        return basics.tril(self, k)

    def triu(self, k=0):
        from .linalg import basics

        return basics.triu(self, k)

    def trunc(self, out=None):
        from . import rounding

        return rounding.trunc(self, out)

    def unique(self, sorted=False, return_inverse=False, axis=None):
        from . import manipulations

        return manipulations.unique(self, sorted, return_inverse, axis)

    def var(self, axis=None, ddof=0, **kwargs):
        from . import statistics

        return statistics.var(self, axis, ddof=ddof, **kwargs)

    # ------------------------------------------------------------------
    # halo exchange (dndarray.py:387-464)
    # ------------------------------------------------------------------
    def get_halo(self, halo_size: int) -> None:
        """Fetch ``halo_size`` rows from the ring neighbors along the split
        axis (dndarray.py:387-464).  The paired Isend/Irecv of the
        reference become slicing against the neighbor chunks of the global
        array; see :mod:`heat_tpu.parallel.halo` for the in-shard_map
        ppermute variant used by collective consumers."""
        if not isinstance(halo_size, int):
            raise TypeError(f"halo_size needs to be an integer, found {type(halo_size)}")
        if halo_size < 0:
            raise ValueError(f"halo_size needs to be a non-negative integer, got {halo_size}")
        if self.__split is None:
            self.__halo_size = 0
            self.__halo_prev = None
            self.__halo_next = None
            return
        # halos slice at CANONICAL chunk boundaries (the compute layout),
        # so validate against the canonical map — an active ragged
        # redistribute_ changes only the reported metadata layout
        canon = self.__comm.lshape_map(self.__gshape, self.__split)
        if halo_size > int(canon[:, self.__split].min()):
            raise ValueError(
                f"halo_size {halo_size} needs to be smaller than the smallest local chunk "
                f"{int(canon[:, self.__split].min())}"
            )
        self.__halo_size = halo_size
        dense = self._dense()
        start, lshape, _ = self.__comm.chunk(self.__gshape, self.__split, rank=self.__comm.rank)
        stop = start + lshape[self.__split]
        s = self.__split

        def _sl(a, b):
            return tuple(slice(a, b) if d == s else slice(None) for d in range(self.ndim))

        self.__halo_prev = dense[_sl(max(start - halo_size, 0), start)] if start > 0 else None
        self.__halo_next = (
            dense[_sl(stop, min(stop + halo_size, self.__gshape[s]))]
            if stop < self.__gshape[s]
            else None
        )

    @property
    def halo_prev(self) -> Optional[jax.Array]:
        return getattr(self, "_DNDarray__halo_prev", None)

    @property
    def halo_next(self) -> Optional[jax.Array]:
        return getattr(self, "_DNDarray__halo_next", None)

    @property
    def array_with_halos(self) -> jax.Array:
        """Local chunk extended by the fetched halos (dndarray.py:360,
        ``__cat_halo`` :465)."""
        pieces = []
        if self.halo_prev is not None:
            pieces.append(self.halo_prev)
        pieces.append(self.larray)
        if self.halo_next is not None:
            pieces.append(self.halo_next)
        if len(pieces) == 1:
            return pieces[0]
        return jnp.concatenate(pieces, axis=self.__split if self.__split is not None else 0)

    def __reduce__(self):
        # pickle via numpy round-trip (the mesh is process-global state)
        from . import factories

        return (_rebuild, (self.numpy(), self.__dtype.__name__, self.__split))


def _rebuild(np_arr, dtype_name, split):
    from . import factories

    return factories.array(np_arr, dtype=getattr(types, dtype_name), split=split)


def _iop(self: DNDarray, result: DNDarray) -> DNDarray:
    if result.shape != self.shape:
        raise ValueError(
            f"non-broadcastable output operand with shape {self.shape} doesn't match the broadcast shape {result.shape}"
        )
    if result.dtype != self.dtype and not types.can_cast(result.dtype, self.dtype):
        raise TypeError(f"cannot cast {result.dtype} back to {self.dtype} for in-place operation")
    if result.split != self.split:
        result = result.resplit(self.split)
    if result is self:
        return self
    jdt = self.dtype.jax_type()
    if (
        result._planar is None
        and not jnp.issubdtype(jdt, jnp.complexfloating)
        and result._padded_shape == self._padded_shape
    ):
        # nobody has asked for the values yet: where the chain reads this
        # array's buffer and no other of its size, the array takes the
        # chain and the store waits for its first reader (larray_padded),
        # so `x -= m; x /= s; x *= s` is one pass over x and not three
        if self._defer_store(result, jdt):
            return self
        # one cached executable: the pending chain (if any) + the cast,
        # donating this array's dead backing buffer when unshared — the
        # `a += b` path aliases a's buffer to the output
        casted = _dispatch.cast_store(
            self._donation_source(), result._fusion_source, jdt,
            self.comm.sharding(self.split),
        )
    else:
        casted = result.larray_padded.astype(jdt)
    self._replace(casted)
    return self


def _pad_to_canonical(
    dense: jax.Array, gshape: Tuple[int, ...], split: Optional[int], comm: Communication
) -> jax.Array:
    """Pad a true-shape array along ``split`` and place with canonical sharding."""
    if split is None:
        return jax.device_put(dense, comm.sharding(None))
    pad = comm.pad_amount(gshape[split])
    if pad:
        widths = [(0, pad if d == split else 0) for d in range(dense.ndim)]
        dense = jnp.pad(dense, widths)
    return jax.device_put(dense, comm.sharding(split))


def _convert_key(arr: DNDarray, key):
    """Normalize an indexing key: DNDarrays -> dense jax arrays; compute the
    output split EXACTLY by walking the key through numpy's indexing rules
    (the analog of the reference's torch meta-proxy, dndarray.py:1855-1863,
    without allocating anything)."""
    split = arr.split

    def conv(k):
        if isinstance(k, DNDarray):
            return k._dense()
        if isinstance(k, list):
            return np.asarray(k)  # numpy allows list keys; jnp does not
        return k

    if isinstance(key, tuple):
        key_t = tuple(conv(k) for k in key)
    else:
        key_t = conv(key)

    return key_t, _exact_out_split(arr, key_t)


def _exact_out_split(arr: DNDarray, key_t) -> Optional[int]:
    """Where the input's split dimension lands in the indexed output.

    Implements numpy's layout rules exactly: ints remove dims, slices map
    them through, newaxis inserts, a boolean mask of ndim k consumes k
    input dims, and the advanced-index broadcast block is placed at the
    position of the first advanced key when the advanced keys are
    adjacent, else at the front.  When the split dim is consumed by an
    integer, the output is no longer distributed along it (None); when it
    feeds the advanced block, the output's split is that block's
    position."""
    split = arr.split
    if split is None:
        return None
    keys = list(key_t) if isinstance(key_t, tuple) else [key_t]
    norm = []
    for k in keys:
        if isinstance(k, (list, tuple)):
            k = np.asarray(k)
        if isinstance(k, (bool, np.bool_)) or (
            isinstance(k, (jax.Array, np.ndarray))
            and k.ndim == 0
            and k.dtype == np.bool_
        ):
            return 0  # scalar-bool key: degenerate advanced case
        norm.append(k)

    def is_array(k):
        return isinstance(k, (jax.Array, np.ndarray))

    def consumed(k) -> int:
        if k is None:
            return 0
        if is_array(k) and k.dtype == np.bool_:
            return int(k.ndim)
        return 1  # int, slice, integer array (incl. 0-d)

    n_explicit = sum(consumed(k) for k in norm if k is not Ellipsis)
    expanded = []
    for k in norm:
        if k is Ellipsis:
            expanded.extend([slice(None)] * (arr.ndim - n_explicit))
        else:
            expanded.append(k)
    expanded.extend(
        [slice(None)] * (arr.ndim - sum(consumed(k) for k in expanded))
    )

    # advanced block: broadcast rank and adjacency
    adv_positions = [i for i, k in enumerate(expanded) if is_array(k)]
    adv_present = bool(adv_positions)
    if adv_present:
        ranks = [
            1 if k.dtype == np.bool_ else int(k.ndim)
            for k in (expanded[i] for i in adv_positions)
        ]
        nb = max(ranks) if ranks else 0
        contiguous = adv_positions[-1] - adv_positions[0] + 1 == len(adv_positions)

    # walk: build the basic output dims in order, find the split's fate
    basic_out = []  # entries: ("in", input_dim) | ("new",)
    first_adv_basic_count = None
    in_dim = 0
    split_fate = "kept"
    for k in expanded:
        if k is None:
            basic_out.append(("new",))
            continue
        if is_array(k):
            if first_adv_basic_count is None:
                first_adv_basic_count = len(basic_out)
            c = consumed(k)
            if in_dim <= split < in_dim + c:
                split_fate = "adv"
            in_dim += c
            continue
        if isinstance(k, slice):
            basic_out.append(("in", in_dim))
            in_dim += 1
            continue
        # integer: removes the dim
        if in_dim == split:
            split_fate = "int"
        in_dim += 1

    if split_fate == "int":
        return None
    if adv_present:
        insert_at = first_adv_basic_count if contiguous else 0
        if split_fate == "adv":
            # nb == 0: only 0-d integer arrays — the dim is removed
            return insert_at if nb > 0 else None
        pos = basic_out.index(("in", split))
        return pos + (nb if pos >= insert_at else 0)
    return basic_out.index(("in", split))
