"""TPU-native communication layer: device meshes instead of MPI communicators.

This is the equivalent of the reference's L1 layer
(``heat/core/communication.py``, ``Communication`` ABC at
communication.py:84-113 and ``MPICommunication`` at :116).  Instead of
wrapping an ``MPI.Comm`` and hand-writing Allreduce/Allgather/Alltoall over
mpi4py buffers, a :class:`Communication` here wraps a 1-D
:class:`jax.sharding.Mesh` over a set of devices.  Collective communication
is never issued explicitly by the ops layer: arrays carry
:class:`jax.sharding.NamedSharding` metadata and XLA/GSPMD inserts the
collectives (psum/all-gather/all-to-all/collective-permute) over ICI/DCN.
Explicit collectives (for halo exchanges, ring algorithms, TS-QR merge
trees) are exposed as thin ``jax.lax`` wrappers intended for use inside
``jax.shard_map`` bodies.

Key translations from the reference:

* ``MPI_WORLD``/``MPI_SELF`` (communication.py:2204-2205) -> :data:`WORLD`
  (a mesh over all devices) / :data:`SELF` (a single-device mesh).
* ``MPICommunication.chunk`` (communication.py:157-214), which computes the
  (offset, local shape, slices) of one rank's block -> :meth:`Communication.chunk`,
  which computes the same for the *canonical padded* distribution used by
  this framework (see below).
* ``Split()`` (communication.py:481) -> :meth:`Communication.split`,
  returning a sub-mesh communication.
* dtype/buffer bridges (communication.py:126-139, :258-333) -> gone; XLA
  owns layout and transport.

Canonical distribution (pad-and-mask)
-------------------------------------
XLA wants equal per-device shards, while the reference's ``chunk()`` hands
out ragged remainder chunks.  We therefore define the canonical distribution
of a global shape ``g`` split along axis ``s`` over ``n`` devices as: pad
``g[s]`` up to the next multiple of ``n``, shard evenly, and keep the true
(unpadded) global shape as metadata.  Real data is a contiguous prefix;
padding is a suffix owned by the highest ranks.  Consumers that reduce or
contract across the split axis mask the padding with their own neutral
element.  For divisible shapes (the common case) no padding exists and no
masking cost is paid.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..resilience.errors import ReshapeError
from ..resilience.faults import inject as _inject
from ..resilience.retry import default_init_policy as _init_policy
from ..telemetry import metrics as _tm
from ..telemetry.spans import span as _span

__all__ = [
    "Communication",
    "HierarchicalCommunication",
    "WORLD",
    "SELF",
    "get_comm",
    "sanitize_comm",
    "use_comm",
    "init",
    "is_initialized",
    "finalize",
    "comm_epoch",
]

#: Name of the mesh axis used for the (single) split dimension, mirroring the
#: reference's one-split-axis model (SURVEY.md L2).
SPLIT_AXIS_NAME = "split"

#: Axis names of the hierarchical (node x local) mesh: 'global' spans nodes
#: (DCN in a multi-slice pod), 'node' spans the devices within one node (ICI).
GLOBAL_AXIS_NAME = "global"
NODE_AXIS_NAME = "node"

# ----------------------------------------------------------------------
# collective volume accounting (telemetry).  Collectives are invoked at
# TRACE time (inside shard_map bodies under jit), so the counts are a
# static model of the compiled program's communication — payload bytes
# x participants per issued collective, not a wire measurement.  A
# program traced once and re-executed from the jit cache accounts its
# collectives exactly once, which is what makes the counts
# deterministic and comparable across runs.
# ----------------------------------------------------------------------
_COMM_COUNTERS: dict = {}


def _comm_counters(op: str):
    pair = _COMM_COUNTERS.get(op)
    if pair is None:
        pair = _COMM_COUNTERS[op] = (
            _tm.counter(f"comm.calls.{op}", f"{op} collectives issued (trace time)"),
            _tm.counter(
                f"comm.bytes.{op}", f"{op} payload bytes x participants (trace time)"
            ),
        )
    return pair


def _payload_nbytes(x) -> int:
    """Total payload bytes of a (possibly traced) array or pytree."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(x):
        try:
            total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        except Exception:  # lint: allow H501(best-effort payload byte model over traced leaves)
            pass
    return total


class Communication:
    """A communication context: an ordered set of devices forming a 1-D mesh.

    Plays the role of the reference's ``MPICommunication``
    (communication.py:116): it defines how a global array is laid out across
    participants and provides the collective primitives.  ``size`` is the
    number of devices in the mesh (the analog of the number of MPI ranks);
    ``rank`` is the index of the calling *process* (0 in single-controller
    mode, where one Python program drives every device).
    """

    def __init__(
        self,
        devices: Optional[Sequence] = None,
        axis_name: str = SPLIT_AXIS_NAME,
    ):
        # ``devices`` may be None (all devices), a sequence, or a zero-arg
        # callable.  Resolution is LAZY so that constructing the module-level
        # WORLD/SELF does not initialize the XLA backend — ``init()`` (the
        # multi-process bootstrap) must run before the first backend touch.
        self._devices_spec = devices
        self.axis_name = axis_name
        self._resolved: Optional[Tuple[List, Mesh]] = None
        self._resolved_epoch: int = -1
        self._retired = False

    def _resolve_devices(self) -> List:
        spec = self._devices_spec
        if spec is None:
            return list(jax.devices())
        if callable(spec):
            return list(spec())
        return list(spec)

    def _reresolvable(self) -> bool:
        """Whether the device set can be recomputed after the runtime's
        device inventory changes (spec-based comms: None / callable).  A
        comm built over an explicit device list is pinned to those
        objects — after ``finalize()``+``init()`` it must be rebuilt via
        :meth:`reshape`, not silently re-pointed."""
        return self._devices_spec is None or callable(self._devices_spec)

    def _build(self) -> Tuple[List, Mesh]:
        devs = self._resolve_devices()
        mesh = Mesh(np.asarray(devs, dtype=object), (self.axis_name,))
        return devs, mesh

    def _ensure(self) -> Tuple[List, Mesh]:
        # Re-resolve after an init()/finalize() cycle bumped the device
        # epoch: the old device objects belong to a dead runtime, and
        # every derived mesh/sharding with them is stale.
        if self._resolved is None or (
            self._resolved_epoch != _EPOCH and self._reresolvable()
        ):
            self._resolved = self._build()
            self._resolved_epoch = _EPOCH
            self._retired = False  # a fresh resolution is a fresh mesh
        return self._resolved

    @property
    def _devices(self) -> List:
        return self._ensure()[0]

    @property
    def _mesh(self) -> Mesh:
        return self._ensure()[1]

    # ------------------------------------------------------------------
    # topology.  Terminology (coherent multi-host semantics):
    #   * participant = one DEVICE in the mesh; ``size``/``chunk(rank=...)``
    #     are in participant units (the analog of an MPI rank's chunk).
    #   * process = one HOST controller (``jax.process_index``); each process
    #     owns a contiguous block of participants.  Single-controller mode is
    #     the special case process_count == 1 owning all participants.
    # ------------------------------------------------------------------
    @property
    def mesh(self) -> Mesh:
        """The underlying 1-D :class:`jax.sharding.Mesh`."""
        return self._mesh

    @property
    def devices(self) -> List:
        return list(self._devices)

    @property
    def size(self) -> int:
        """Number of participants (devices), analog of ``MPI.Comm.size``."""
        return len(self._devices)

    @property
    def rank(self) -> int:
        """Index of the calling *process* (``jax.process_index``), the analog
        of the reference's ``comm.rank`` when one interpreter == one MPI rank
        (communication.py:116).  For the participant (device) view use
        ``chunk(rank=...)`` / ``local_participants``.
        """
        return jax.process_index()

    process_rank = rank

    @property
    def process_count(self) -> int:
        """Number of host controllers driving this mesh."""
        return jax.process_count()

    @property
    def local_participants(self) -> List[int]:
        """Participant (device) indices owned by the calling process."""
        pid = jax.process_index()
        return [i for i, d in enumerate(self._devices) if d.process_index == pid]

    @property
    def local_devices(self) -> List:
        """The calling process's addressable devices within this mesh."""
        return [d for d in self._devices if d.process_index == jax.process_index()]

    @property
    def process_blocks_contiguous(self) -> bool:
        """True when every process's devices occupy one contiguous run of
        participant indices (the canonical WORLD layout).  Host-local data
        placement (``make_array_from_process_local_data``) requires this;
        interleaved sub-meshes fall back to callback-based placement."""
        owners = {}
        for i, d in enumerate(self._devices):
            owners.setdefault(d.process_index, []).append(i)
        return all(v == list(range(v[0], v[-1] + 1)) for v in owners.values())

    @property
    def is_distributed(self) -> bool:
        """Analog of ``Communication.is_distributed`` (communication.py:95)."""
        return self.size > 1

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, Communication)
            and self._devices == other._devices
            and self.axis_name == other.axis_name
        )

    def __hash__(self) -> int:
        return hash((tuple(id(d) for d in self._devices), self.axis_name))

    def __repr__(self) -> str:
        plat = self._devices[0].platform if self._devices else "?"
        return f"Communication(size={self.size}, platform={plat!r})"

    # ------------------------------------------------------------------
    # sharding / chunking policy
    # ------------------------------------------------------------------
    def sharding(self, split: Optional[int], ndim: Optional[int] = None) -> NamedSharding:
        """NamedSharding for an array split along ``split`` (None=replicated)."""
        if split is None:
            spec = PartitionSpec()
        else:
            spec = PartitionSpec(*((None,) * split), self.axis_name)
        return NamedSharding(self._mesh, spec)

    def pad_amount(self, extent: int) -> int:
        """Padding needed to make ``extent`` divisible by ``size``."""
        return (-extent) % self.size

    def padded_extent(self, extent: int) -> int:
        return extent + self.pad_amount(extent)

    def chunk(
        self, shape: Sequence[int], split: Optional[int], rank: Optional[int] = None
    ) -> Tuple[int, Tuple[int, ...], Tuple[slice, ...]]:
        """Compute one participant's block of the canonical distribution.

        Returns ``(offset, local_shape, slices)`` like the reference's
        ``MPICommunication.chunk`` (communication.py:157-214).  Unlike the
        reference — which spreads the remainder over the low ranks — the
        canonical distribution here gives every participant
        ``ceil(extent / size)`` rows with trailing padding, so the *true*
        local shape of high ranks may be smaller or zero.
        """
        shape = tuple(int(s) for s in shape)
        if split is None:
            return 0, shape, tuple(slice(0, s) for s in shape)
        rank = self.rank if rank is None else rank
        extent = shape[split]
        per = self.padded_extent(extent) // self.size
        start = min(rank * per, extent)
        stop = min(start + per, extent)
        lshape = shape[:split] + (stop - start,) + shape[split + 1 :]
        slices = tuple(
            slice(start, stop) if dim == split else slice(0, s)
            for dim, s in enumerate(shape)
        )
        return start, lshape, slices

    def process_chunk(
        self, shape: Sequence[int], split: Optional[int], process: Optional[int] = None
    ) -> Tuple[int, Tuple[int, ...], Tuple[slice, ...]]:
        """One *process's* block: the union of its participants' chunks.

        The multi-host analog of the reference's ``chunk`` (one MPI rank ==
        one interpreter, communication.py:157): a process owns the contiguous
        row range covered by its devices' canonical shards.
        """
        shape = tuple(int(s) for s in shape)
        if split is None:
            return 0, shape, tuple(slice(0, s) for s in shape)
        process = jax.process_index() if process is None else process
        parts = [i for i, d in enumerate(self._devices) if d.process_index == process]
        if parts and parts != list(range(parts[0], parts[-1] + 1)):
            raise NotImplementedError(
                "process_chunk requires each process's devices to occupy a "
                "contiguous run of participant indices (see "
                "process_blocks_contiguous); interleaved sub-meshes are not "
                "supported"
            )
        if not parts:
            lshape = shape[:split] + (0,) + shape[split + 1 :]
            return 0, lshape, tuple(
                slice(0, 0) if d == split else slice(0, s) for d, s in enumerate(shape)
            )
        per = self.padded_extent(shape[split]) // self.size
        start = min(min(parts) * per, shape[split])
        stop = min((max(parts) + 1) * per, shape[split])
        lshape = shape[:split] + (stop - start,) + shape[split + 1 :]
        slices = tuple(
            slice(start, stop) if dim == split else slice(0, s)
            for dim, s in enumerate(shape)
        )
        return start, lshape, slices

    def lshape_map(self, shape: Sequence[int], split: Optional[int]) -> np.ndarray:
        """(size, ndim) array of true local shapes per participant.

        Analog of ``DNDarray.lshape_map`` (dndarray.py:304) but computed
        purely from metadata — no communication is ever required because the
        canonical distribution is a pure function of (shape, split, size).
        """
        shape = tuple(int(s) for s in shape)
        out = np.empty((self.size, max(len(shape), 1)), dtype=np.int64)
        for r in range(self.size):
            _, lshape, _ = self.chunk(shape, split, rank=r)
            out[r, : len(shape)] = lshape
        return out[:, : len(shape)]

    def counts_displs_shape(
        self, shape: Sequence[int], axis: int
    ) -> Tuple[Tuple[int, ...], Tuple[int, ...], Tuple[int, ...]]:
        """Counts/displacements along ``axis``, analog of
        communication.py:216-244 (used there to build Allgatherv/Scatterv
        calls; kept here for lshape bookkeeping and io slab reads)."""
        counts = []
        displs = []
        for r in range(self.size):
            off, lsh, _ = self.chunk(shape, axis, rank=r)
            counts.append(lsh[axis])
            displs.append(off)
        _, lshape, _ = self.chunk(shape, axis, rank=self.rank)
        return tuple(counts), tuple(displs), tuple(lshape)

    # ------------------------------------------------------------------
    # sub-communicators
    # ------------------------------------------------------------------
    def split(self, color_ranks: Sequence[int], axis_name: Optional[str] = None) -> "Communication":
        """Sub-communication over a subset of devices.

        Analog of ``MPICommunication.Split`` (communication.py:481): instead
        of a color/key pair, the caller names the member device indices
        directly (SPMD single-controller has global knowledge).
        """
        devs = [self._devices[i] for i in color_ranks]
        return Communication(devs, axis_name or self.axis_name)

    # ------------------------------------------------------------------
    # elastic reshape
    # ------------------------------------------------------------------
    @property
    def retired(self) -> bool:
        """True once :meth:`reshape` replaced this mesh.  A retired comm
        stays readable (its chunk/lshape metadata describes arrays not
        yet re-split) but should not receive new work."""
        return self._retired

    def _surviving_devices(self, n_devices: Optional[int], devices) -> List:
        """Resolve the survivor set for :meth:`reshape` and validate it
        against the runtime's current device inventory."""
        available = list(jax.devices())
        if devices is not None:
            devs = list(devices)
            alive = {id(d) for d in available}
            missing = [d for d in devs if id(d) not in alive]
            if missing:
                raise ReshapeError(
                    f"reshape target names {len(missing)} device(s) not in the "
                    f"current runtime inventory ({len(available)} available)",
                    old_size=self.size, new_size=len(devs),
                )
            if not devs:
                raise ReshapeError(
                    "reshape target is empty", old_size=self.size, new_size=0
                )
            return devs
        if n_devices is None:
            raise ReshapeError(
                "reshape needs n_devices or an explicit device list",
                old_size=self.size,
            )
        n = int(n_devices)
        if n < 1:
            raise ReshapeError(
                f"reshape target world size must be >= 1, got {n}",
                old_size=self.size, new_size=n,
            )
        if n > len(available):
            raise ReshapeError(
                f"reshape target world size {n} exceeds the {len(available)} "
                "devices the runtime currently exposes",
                old_size=self.size, new_size=n,
            )
        # prefer this comm's own surviving devices (stable participant
        # order for the unaffected prefix), then draw replacements from
        # the runtime inventory (capacity that came back elsewhere)
        alive = {id(d) for d in available}
        survivors = [d for d in self._devices if id(d) in alive]
        if len(survivors) < n:
            have = {id(d) for d in survivors}
            survivors += [d for d in available if id(d) not in have]
        return survivors[:n]

    def reshape(self, n_devices: Optional[int] = None, devices=None) -> "Communication":
        """Rebuild this communication for a different world size.

        The elastic-recovery primitive (docs/elasticity.md): after a
        worker loss (or regrowth) the caller asks for a mesh over the
        surviving ``n_devices`` — preferring this comm's own devices
        that are still alive, topped up from the runtime inventory — and
        receives a NEW :class:`Communication`.  All distribution
        metadata (``chunk``/``lshape_map``/``sharding``/
        ``counts_displs_shape``) is a pure function of (shape, split,
        size), so it is implicitly recomputed for the new world; live
        arrays must be re-materialized onto the new comm
        (``DNDarray.reshard_``, or a cross-world
        ``Checkpointer.restore(..., comm=new)``).

        The old comm is marked retired but stays readable — its metadata
        still describes the not-yet-resharded arrays.  Raises
        :class:`~heat_tpu.resilience.errors.ReshapeError` for an
        impossible target (empty, larger than the runtime inventory,
        dead explicit devices)."""
        devs = self._surviving_devices(n_devices, devices)
        with _span("comm.reshape", old=self.size, new=len(devs)):
            axis = self.axis_name if isinstance(self.axis_name, str) else SPLIT_AXIS_NAME
            new = Communication(devs, axis)
            new._ensure()  # build the mesh now: fail fast, not at first use
        self._retired = True
        return new

    # ------------------------------------------------------------------
    # explicit collectives — for use inside jax.shard_map bodies only.
    # The ops layer almost never needs these; GSPMD infers communication
    # from shardings.  They exist for halo exchange, ring algorithms and
    # merge trees (TS-QR / hSVD), replacing the reference's hand-written
    # Send/Recv/Allreduce/... (communication.py:494-2186).
    # Every entry evaluates the ``comm.collective`` fault-injection
    # point (trace-time, so the compiled program itself is unaffected) —
    # the hook a fault plan uses to script a lost-collective scenario —
    # and accounts its payload into the telemetry registry
    # (``comm.bytes.{op}`` / ``comm.calls.{op}``, see the module-level
    # accounting note) while running under a ``comm.{op}`` span.
    # ------------------------------------------------------------------
    def _axis_size(self, axis_name) -> int:
        """Participant count along ``axis_name`` (axis-name tuples — the
        hierarchical default — multiply out)."""
        names = axis_name if isinstance(axis_name, tuple) else (axis_name,)
        try:
            shape = dict(self.mesh.shape)
            n = 1
            for nm in names:
                n *= int(shape.get(nm, 1))
            return n
        except Exception:  # lint: allow H501(mesh-shape probe falls back to comm size)
            return self.size

    def _account(self, op: str, x, axis_name):
        """Record one issued collective; returns a ``comm.{op}`` span
        (trace-time wall clock) carrying the byte model as attrs."""
        _inject("comm.collective", op=op)
        participants = self._axis_size(axis_name)
        nbytes = _payload_nbytes(x) * participants
        calls, byts = _comm_counters(op)
        calls.inc()
        byts.inc(nbytes)
        return _span(f"comm.{op}", bytes=nbytes, participants=participants)

    def account_implicit(self, op: str, nbytes: int, axis_name=None, **attrs):
        """Account a GSPMD-*inferred* collective this layer never issues
        explicitly — e.g. the psum XLA inserts behind a segment sum over
        the split axis in the kmeans centroid update.  Same counters and
        ``comm.{op}`` span as the explicit collectives (the span attrs
        carry ``implicit=True``); ``nbytes`` is the per-participant
        payload, scaled by the participant count like the explicit
        model.  Returns the span as a context manager — wrap the
        launching call so the trace attributes the program to it."""
        participants = self._axis_size(axis_name or self.axis_name)
        total = int(nbytes) * participants
        calls, byts = _comm_counters(op)
        calls.inc()
        byts.inc(total)
        return _span(
            f"comm.{op}", bytes=total, participants=participants,
            implicit=True, **attrs,
        )

    def psum(self, x, axis_name: Optional[str] = None):
        name = axis_name or self.axis_name
        with self._account("psum", x, name):
            return jax.lax.psum(x, name)

    def pmax(self, x, axis_name: Optional[str] = None):
        name = axis_name or self.axis_name
        with self._account("pmax", x, name):
            return jax.lax.pmax(x, name)

    def pmin(self, x, axis_name: Optional[str] = None):
        name = axis_name or self.axis_name
        with self._account("pmin", x, name):
            return jax.lax.pmin(x, name)

    def all_gather(self, x, axis: int = 0, axis_name: Optional[str] = None, tiled: bool = True):
        name = axis_name or self.axis_name
        with self._account("all_gather", x, name):
            return jax.lax.all_gather(x, name, axis=axis, tiled=tiled)

    def all_to_all(self, x, split_axis: int, concat_axis: int, axis_name: Optional[str] = None):
        name = axis_name or self.axis_name
        with self._account("all_to_all", x, name):
            return jax.lax.all_to_all(
                x, name, split_axis=split_axis, concat_axis=concat_axis, tiled=True,
            )

    def overlap_compiler_options(self) -> dict:
        """Compile options (``jax.jit(..., compiler_options=...)``) of a
        program that issues ``all_to_all`` on independent blocks and wants
        each exchange in flight while the other blocks compute: on a TPU mesh
        the compiler then emits ``all-to-all-start`` / ``-done`` pairs and
        schedules the blocks' fusions between them; without the option every
        all-to-all is one synchronous operation however the program is cut
        (PERF.md section 6, PR 32).  Empty on other backends, whose compilers
        refuse the name."""
        if self.devices[0].platform == "tpu":
            return {"xla_tpu_enable_async_all_to_all": True}
        return {}

    def psum_scatter(self, x, axis_name: Optional[str] = None, scatter_dimension: int = 0):
        """Reduce-scatter: the sum lands shard-wise instead of replicated
        (the reference's Reduce_scatter, communication.py; the sparse
        SpMM meet-step uses it directly)."""
        name = axis_name or self.axis_name
        with self._account("psum_scatter", x, name):
            return jax.lax.psum_scatter(
                x, name, scatter_dimension=scatter_dimension, tiled=True,
            )

    def pscan(self, x, axis_name: Optional[str] = None, inclusive: bool = True):
        """Prefix sum over mesh ranks (the reference's Scan / Exscan,
        communication.py:2010-2086) as log2(size) ``ppermute`` rounds —
        ranks outside a round's permutation receive zeros, which is the
        additive identity, so no masking is needed.  The round count and
        rank range come from the NAMED axis (an override may address a
        sub-axis whose size differs from ``self.size``)."""
        name = axis_name or self.axis_name
        n = int(dict(self.mesh.shape)[name]) if name != self.axis_name else self.size
        # one account entry covers the whole log2(n)-round ladder (plus
        # the shift round of an exclusive scan): bytes scale by rounds
        rounds = max(n - 1, 0).bit_length() + (0 if inclusive else 1)
        op = "pscan" if inclusive else "exscan"
        with self._account(op, [x] * rounds, name):
            acc = x
            shift = 1
            while shift < n:
                prev = jax.lax.ppermute(
                    acc, name, [(i, i + shift) for i in range(n - shift)]
                )
                acc = acc + prev
                shift *= 2
            if inclusive:
                return acc
            # exclusive scan: the inclusive result of the previous rank
            # (rank 0 receives the zero fill — MPI's Exscan leaves rank 0
            # undefined; zero is this layer's defined value)
            return jax.lax.ppermute(acc, name, [(i, i + 1) for i in range(n - 1)])

    def exscan(self, x, axis_name: Optional[str] = None):
        """Exclusive prefix sum (zero at rank 0)."""
        return self.pscan(x, axis_name, inclusive=False)

    def ppermute(self, x, perm, axis_name: Optional[str] = None):
        name = axis_name or self.axis_name
        with self._account("ppermute", x, name):
            return jax.lax.ppermute(x, name, perm=perm)

    def ring_shift(self, x, shift: int = 1, axis_name: Optional[str] = None):
        """Cyclic shift by ``shift`` ranks (the ring primitive behind the
        reference's spatial ring in distance.py:209 and roll)."""
        name = axis_name or self.axis_name
        n = self.size
        perm = [(i, (i + shift) % n) for i in range(n)]
        with self._account("ring_shift", x, name):
            return jax.lax.ppermute(x, name, perm=perm)

    def axis_index(self, axis_name: Optional[str] = None):
        return jax.lax.axis_index(axis_name or self.axis_name)


class HierarchicalCommunication(Communication):
    """A 2-axis (n_node, per_node) device grid for hierarchical parallelism.

    The analog of the reference DASO's two-level communicator pair
    (``heat/optim/dp_optimizer.py:64``: torch-DDP process groups within a
    node + an MPI world across nodes, ``:450`` ``_global_sync``).  Here the
    hierarchy is a property of the mesh: axis ``'global'`` (size
    ``n_node``) spans nodes and rides DCN on a multi-slice pod; axis
    ``'node'`` (size ``per_node``) spans the devices within one node and
    rides ICI.  A collective over ``'node'`` is the reference's node-local
    DDP allreduce; a collective over ``'global'`` is the reference's
    cross-node MPI averaging.

    Used as a drop-in :class:`Communication` for ordinary split arrays, the
    split dimension shards over BOTH axes (the flattened participant
    order), so every factory/op works unchanged on a hierarchical comm.
    """

    def __init__(
        self,
        grid: Optional[Tuple[int, int]] = None,
        devices: Optional[Sequence] = None,
        axis_names: Tuple[str, str] = (GLOBAL_AXIS_NAME, NODE_AXIS_NAME),
    ):
        self._grid_spec = grid
        self._axis_names = tuple(axis_names)
        # axis_name is the tuple of both axes: PartitionSpec and
        # psum/all_gather accept axis-name tuples, so the base class's
        # sharding()/collectives shard/reduce over the flattened grid.
        super().__init__(devices=devices, axis_name=self._axis_names)

    @staticmethod
    def infer_grid(devices: Sequence) -> Tuple[int, int]:
        """(n_node, per_node) for a device set: one 'node' per host
        process (the reference's node==host assumption) when that tiles
        the set evenly; a single host degenerates to ``(1, n)``."""
        nproc = len({d.process_index for d in devices})
        if nproc > 1 and len(devices) % nproc == 0:
            return (nproc, len(devices) // nproc)
        return (1, len(devices))

    def _build(self) -> Tuple[List, Mesh]:
        devs = self._resolve_devices()
        grid = self._grid_spec
        if grid is None:
            grid = self.infer_grid(devs)
        n_node, per_node = int(grid[0]), int(grid[1])
        if n_node * per_node != len(devs):
            raise ValueError(
                f"grid {grid} does not tile {len(devs)} devices"
            )
        arr = np.asarray(devs, dtype=object).reshape(n_node, per_node)
        mesh = Mesh(arr, self._axis_names)
        return devs, mesh

    # -- hierarchy topology --------------------------------------------
    @property
    def global_axis(self) -> str:
        """Mesh axis spanning nodes (DCN)."""
        return self._axis_names[0]

    @property
    def node_axis(self) -> str:
        """Mesh axis spanning a node's devices (ICI)."""
        return self._axis_names[1]

    @property
    def num_nodes(self) -> int:
        return self._mesh.shape[self._axis_names[0]]

    @property
    def node_size(self) -> int:
        return self._mesh.shape[self._axis_names[1]]

    def node_sharding(self) -> NamedSharding:
        """Sharding for per-node stacked pytrees: leading dim = node index,
        sharded over 'global'; everything else replicated."""
        return NamedSharding(self._mesh, PartitionSpec(self.global_axis))

    def split(self, color_ranks: Sequence[int], axis_name: Optional[str] = None) -> Communication:
        """Sub-communication over a device subset.  A subset of a grid is
        not itself a grid, so the result is a flat 1-D Communication (the
        reference's Split likewise returns a plain communicator)."""
        devs = [self._devices[i] for i in color_ranks]
        return Communication(devs, axis_name or SPLIT_AXIS_NAME)

    def reshape(
        self, n_devices: Optional[int] = None, devices=None
    ) -> "HierarchicalCommunication":
        """Rebuild the (ICI-node x DCN-global) grid for the surviving
        device set: the node structure is re-inferred from the
        survivors' host processes (:meth:`infer_grid`), NOT carried over
        — losing a worker usually leaves a partial node, and a stale
        grid would put cross-host hops on the 'node' (ICI) axis."""
        devs = self._surviving_devices(n_devices, devices)
        with _span("comm.reshape", old=self.size, new=len(devs), hierarchical=True):
            new = HierarchicalCommunication(
                grid=self.infer_grid(devs), devices=devs, axis_names=self._axis_names
            )
            new._ensure()
        self._retired = True
        return new

    def __eq__(self, other) -> bool:
        # same devices in a different (n_node, per_node) layout is a
        # DIFFERENT topology: collectives over 'node'/'global' change
        return (
            isinstance(other, HierarchicalCommunication)
            and super().__eq__(other)
            and (self.num_nodes, self.node_size) == (other.num_nodes, other.node_size)
        )

    def __hash__(self) -> int:
        return super().__hash__()

    def __repr__(self) -> str:
        plat = self._devices[0].platform if self._devices else "?"
        return (
            f"HierarchicalCommunication(nodes={self.num_nodes}, "
            f"per_node={self.node_size}, platform={plat!r})"
        )


# ----------------------------------------------------------------------
# multi-process bootstrap, the analog of the reference's implicit MPI_Init
# (importing heat initializes MPI via mpi4py; here the runtime is explicit:
# call ``heat_tpu.parallel.init(...)`` before any array work, mirroring
# ``jax.distributed.initialize``'s own contract)
# ----------------------------------------------------------------------
_initialized = False

#: device-inventory epoch: bumped whenever init()/finalize() (may have)
#: changed the runtime's device set.  Spec-based comms (WORLD/SELF and
#: any Communication built without an explicit device list) lazily
#: re-resolve when their stored epoch is stale, so repeated
#: finalize()+init() cycles — the elastic supervisor's restart path —
#: never leave a mesh pointing at a dead runtime's device objects.
_EPOCH = 0


def comm_epoch() -> int:
    """Current device-inventory epoch (see :data:`_EPOCH`)."""
    return _EPOCH


def init(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids=None,
    **kwargs,
) -> None:
    """Bootstrap multi-host SPMD execution.

    Wraps :func:`jax.distributed.initialize` (the moral equivalent of the
    reference's MPI world bootstrap, communication.py:116 + quick_start's
    ``mpirun -n N python prog.py``): every host runs the same program, and
    after ``init`` the default WORLD communication spans the global device
    set.  Must be called before the first array operation (JAX requires the
    distributed runtime to exist before the backend is initialized).  On a
    single host with no coordinator this is a no-op, so programs written for
    multi-host run unchanged in single-controller mode.

    The bootstrap runs under the init retry policy
    (``resilience.default_init_policy``: bounded exponential backoff,
    ``HEAT_TPU_INIT_RETRY_*`` env knobs) — at pod startup the
    coordinator routinely comes up seconds after the workers, and a
    connection race must not abort the whole program.  Configuration
    errors (no cluster to detect, bad arguments) are not retried.
    """
    global _initialized
    if (
        coordinator_address is None
        and num_processes is None
        and process_id is None
        and local_device_ids is None
        and not kwargs
    ):
        # Zero-arg bootstrap: let jax auto-detect a cluster environment
        # (SLURM, Open MPI, Cloud TPU pod).  On a plain single host there is
        # nothing to detect — initialize() raises the "could not detect"
        # error and this becomes a no-op, so single-host programs need no
        # special-casing.  A detected-but-unreachable cluster (bad
        # coordinator port, network failure) must fail LOUDLY — silently
        # degrading to independent single-process worlds would make every
        # collective return per-host partial results.
        def _bootstrap_auto() -> bool:
            _inject("comm.init")
            try:
                jax.distributed.initialize()
            except (ValueError, RuntimeError) as e:
                msg = str(e).lower()
                # no cluster detected (plain single host): harmless no-op
                no_cluster = "coordinator" in msg and (
                    "defined" in msg or "detect" in msg or "none" in msg or "specif" in msg
                )
                # backend already up on a lone host: a defensive init() call
                # after array work — also harmless.  On a real multi-process
                # run either failure must propagate: silently degrading to
                # independent single-process worlds corrupts every collective.
                late_single_host = "before any jax" in msg and jax.process_count() == 1
                if no_cluster or late_single_host:
                    return False  # benign no-op, nothing to re-resolve
                raise  # real bootstrap failure: retried, then propagates
            return True

        if _init_policy().call(_bootstrap_auto):
            _reset_defaults()
        _initialized = True
        return

    def _bootstrap_explicit() -> None:
        _inject("comm.init")
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            local_device_ids=local_device_ids,
            **kwargs,
        )

    _init_policy().call(_bootstrap_explicit)
    _initialized = True
    _reset_defaults()


def is_initialized() -> bool:
    """Whether :func:`init` has run (``MPI.Is_initialized`` analog)."""
    return _initialized


def finalize() -> None:
    """Tear down the distributed runtime (``MPI_Finalize`` analog).

    Safe for repeated ``finalize()`` + ``init()`` cycles (the elastic
    supervisor's restart path): beyond shutting the runtime down, it
    bumps the device-inventory epoch so spec-based comms re-resolve,
    resets the default comm, and drops every process cache keyed on the
    dead mesh's device objects (compiled-executable dispatch cache and
    its cost records, the FFT weight cache's device-placed constants)."""
    global _initialized
    if jax.process_count() > 1:  # pragma: no cover - multi-host only
        jax.distributed.shutdown()
    _initialized = False
    _reset_defaults()


def _reset_defaults() -> None:
    """Invalidate device-derived state after the device set (may have)
    changed: post-``init`` bootstrap and ``finalize`` teardown."""
    global __default_comm, _EPOCH
    _EPOCH += 1
    WORLD._resolved = None
    SELF._resolved = None
    __default_comm = WORLD
    # compiled executables and device-placed constants are keyed on
    # shardings whose meshes hold the previous epoch's device objects:
    # entries can never hit again and pin a dead runtime's buffers
    try:
        from ..core import dispatch as _dispatch

        _dispatch.clear_cache()
    except Exception:  # lint: allow H501(cache drop is best-effort during teardown)
        pass
    try:
        from ..fft._weight_cache import weight_cache_clear

        weight_cache_clear()
    except Exception:  # lint: allow H501(cache drop is best-effort during teardown)
        pass


# ----------------------------------------------------------------------
# module-level default communications, mirroring communication.py:2204-2251
# (device resolution is lazy — see Communication.__init__)
# ----------------------------------------------------------------------
WORLD = Communication()
SELF = Communication(lambda: jax.devices()[:1])

__default_comm = WORLD


def get_comm() -> Communication:
    """The current default communication (communication.py:2211)."""
    return __default_comm


def sanitize_comm(comm: Optional[Communication]) -> Communication:
    """Validate ``comm`` or fall back to the default (communication.py:2224)."""
    if comm is None:
        return get_comm()
    if not isinstance(comm, Communication):
        raise TypeError(f"Unknown communication, must be instance of Communication, got {type(comm)}")
    return comm


def use_comm(comm: Optional[Communication] = None) -> None:
    """Set the default communication (communication.py:2241)."""
    global __default_comm
    __default_comm = sanitize_comm(comm)
