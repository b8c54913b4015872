"""KMedians clustering, analog of heat/cluster/kmedians.py (kmedians.py:11).

Centers update to the per-cluster feature-wise median instead of the mean.
The fit loop makes one copy of the points, column by column with every
register full (`kernels.pack_columns`), and an iteration is one assignment
over it (Manhattan distances, first index on ties, no ``rows x clusters``
array written) and ONE grouped selection of every cluster's median:
`statistics._select_ranks` with the labels as the group, counting passes over
the order key that all clusters share and one more for the upper neighbours
(the two bodies of the kernel, `kernels.grouped_digit_counts` and
`kernels.grouped_neighbours`: the selection reads the points through them
alone), no sorted copy and no masked copy of the points.  Over a mesh each
device counts in its own rows and the counts are all-reduced.
"""

from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as _P

from ..core import dispatch, kernels, statistics, types
from ..core.dndarray import DNDarray
from ..spatial import distance
from ..telemetry.spans import span as _span
from ._kcluster import _KCluster

__all__ = ["KMedians"]


def _own(a):
    """What a reduction over the rows is where one device holds them all."""
    return a


def _columns(xp, n_true: int, first):
    """The points column by column, every register full (`kernels.pack_columns`:
    ``(features, R, lanes)``), and which of the copy's places hold a real row:
    not the zeros behind the block's last row, nor the array's padding behind
    row ``n_true`` (the block starts at global row ``first``).  The copy is
    made once a fit, as KMeans makes its own: every pass of the loop reads it,
    three values of four where the table as it lies gives three of eight."""
    # behind a barrier: left to itself the compiler reads the table through
    # the copy's definition, a sum along the features a center and a read each
    cols = jax.lax.optimization_barrier(kernels.pack_columns(xp))
    place = (jax.lax.broadcasted_iota(jnp.int32, cols.shape[1:], 0) * cols.shape[2]
             + jax.lax.broadcasted_iota(jnp.int32, cols.shape[1:], 1))
    return cols, place < jnp.minimum(xp.shape[0], n_true - first)


def _nearest(cols, centers, real, want_labels: bool):
    """Every row's nearest center by Manhattan distance, the first on ties
    (``want_labels``), or that distance, in the shape of one packed column: a
    running minimum over the centers, so that the distances to all centers
    are never an array and the whole is one fusion, one read of the points (as
    a ``rows x clusters`` matrix and its ``argmin`` the compiler writes the
    matrix out; labels AND distances asked of one fusion make it write the
    distances to every center: PERF.md, PR 37).  Places that hold no row
    (not ``real``) belong to no cluster: label ``k``, distance 0.  A row that
    holds a NaN compares under nothing and stays with the first center, as
    ``argmin`` has it."""
    def l1(c):
        return functools.reduce(jnp.add, [jnp.abs(cols[i] - c[i]) for i in range(cols.shape[0])])

    with jax.named_scope("kmedians.assign"):
        best = l1(centers[0])
        labels = jnp.zeros_like(best, jnp.int32)
        for j in range(1, centers.shape[0]):
            d = l1(centers[j])
            closer = d < best
            labels = jnp.where(closer, jnp.int32(j), labels)
            best = jnp.where(closer, d, best)
        return jnp.where(real, labels, jnp.int32(centers.shape[0])) if want_labels else jnp.where(real, best, 0)


def _medians(cols, labels, centers, all_sum, all_min):
    """Every cluster's feature-wise median of its members: numpy's rule (the
    element of rank ``(count - 1) // 2``, or the mean of it and the next one
    where the count is even; NaN where a member is), exact, all clusters in
    the same counting passes.  The ranks are device values: a cluster's count
    is known only after the assignment, and the selection's first pass counts
    it (no pass over the labels here).  An empty cluster keeps its center."""
    k = centers.shape[0]
    with jax.named_scope("kmedians.select"):
        low, high, nans, sizes = (v[:, :, 0, 0] for v in statistics._select_ranks(
            cols, (1, 2), lambda sizes: (jnp.maximum(sizes, 1) - 1) // 2, True, None, all_sum, all_min, group=(labels, k)))
        median = jnp.where(sizes % 2 == 0, 0.5 * (low + high), low)
        median = jnp.where(nans > 0, jnp.nan, median).astype(centers.dtype)
        return jnp.where(sizes > 0, median, centers)


def passes_an_iteration(dtype, k: int) -> int:
    """Reads of the points one iteration makes: the assignment's and the
    selection's (``tests/test_chip_compile.py`` counts them in the program
    compiled for the chip)."""
    return 1 + statistics._select_passes(dtype, k, True, grouped=True)


def _loop(xp, centers, n_true: int, first, max_iter: int, tol: float, all_sum, all_min):
    """The whole fit as one on-device ``lax.while_loop`` over the packed copy
    of the points: the convergence test runs on the device and the host never
    reads inside.  Returns (centers, n_iter, last_shift); the shift lets the
    chunked checkpoint/resume driver distinguish convergence from a
    chunk-boundary stop."""
    cols, real = _columns(xp, n_true, first)

    def cond(carry):
        _, i, shift, _ = carry
        return jnp.logical_and(i < max_iter, shift > tol)

    def body(carry):
        c, i, _, x = carry
        # the labels are written once and read by every pass (with the pass
        # left to take them in, the compiler does the assignment again there)
        labels = jax.lax.optimization_barrier(_nearest(x, c, real, want_labels=True))
        new = _medians(x, labels, c, all_sum, all_min)
        # the points go round with the centers: as a constant of the loop the
        # compiler takes what depends on them alone (the order key) out of it
        # and keeps it, one more copy
        return new, i + 1, jnp.sum((new - c) ** 2).astype(jnp.float32), jax.lax.optimization_barrier(x)

    start = (centers, jnp.int32(0), jnp.asarray(jnp.inf, jnp.float32), cols)
    return jax.lax.while_loop(cond, body, start)[:3]


def _final(xp, centers, n_true: int, first, all_sum):
    """The last pass: every row's label and the inertia, the sum of the
    squared distances to the nearest center, from the loop's own assignment."""
    cols, real = _columns(xp, n_true, first)
    # two reads of the points, each one fusion; the second sees the points
    # behind a barrier, or the compiler joins the two and writes the distances
    # to every center between them
    labels, cols = jax.lax.optimization_barrier((_nearest(cols, centers, real, want_labels=True), cols))
    best = _nearest(cols, centers, real, want_labels=False)
    with jax.named_scope("kmedians.assign"):
        return labels.reshape(-1)[: xp.shape[0]], all_sum(jnp.sum(best * best))


@functools.lru_cache(maxsize=64)
def _programs(comm, n_true: int, max_iter: int, tol: float):
    """The fit's two programs, (loop, final pass), on padded points of which
    the first ``n_true`` rows are real.  ``comm`` None: one device, or points
    no mesh divides by rows.  Over a mesh that divides the rows: each device
    assigns and counts in its own rows, the counts and the neighbours' minima
    are all-reduced (``(clusters, digits, features)`` integers a pass), and
    nothing is gathered or sorted across devices."""
    if comm is None:
        return (jax.jit(lambda xp, centers: _loop(xp, centers, n_true, 0, max_iter, tol, _own, _own)),
                jax.jit(lambda xp, centers: _final(xp, centers, n_true, 0, _own)))
    by_rows = _P(comm.axis_name)

    def first(block):
        return jax.lax.axis_index(comm.axis_name) * block.shape[0]

    def loop(block, centers):
        return _loop(block, centers, n_true, first(block), max_iter, tol, comm.psum, comm.pmin)

    def final(block, centers):
        return _final(block, centers, n_true, first(block), comm.psum)

    # `check_vma` off: the counting kernel's results carry no such mark
    return (jax.jit(_shard_map(loop, mesh=comm.mesh, in_specs=(by_rows, _P()), out_specs=_P(), check_vma=False)),
            jax.jit(_shard_map(final, mesh=comm.mesh, in_specs=(by_rows, _P()), out_specs=(by_rows, _P()), check_vma=False)))


def _kmedians_loop(xp: jax.Array, centers: jax.Array, *, n_true: int, max_iter: int, tol: float, comm=None):
    """The fit loop on the padded points: (centers, n_iter, last shift).  ONE
    launch, whatever the mesh; looked up by name where ``fit`` calls it."""
    return _programs(comm, n_true, max_iter, tol)[0](xp, centers)


def _kmedians_assign(xp: jax.Array, centers: jax.Array, *, n_true: int, comm=None):
    """The final pass on the padded points: (labels of the padded rows, inertia)."""
    return _programs(comm, n_true, 0, 0.0)[1](xp, centers)


class KMedians(_KCluster):
    """K-Medians with manhattan assignment (kmedians.py:11)."""

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, DNDarray] = "random",
        max_iter: int = 300,
        tol: float = 1e-4,
        random_state: Optional[int] = None,
        checkpoint_every: Optional[int] = None,
        checkpoint_dir: Optional[str] = None,
        resume_from: Optional[str] = None,
    ):
        if isinstance(init, str) and init == "kmedians++":
            init = "probability_based"
        super().__init__(
            metric=lambda x, y: distance.manhattan(x, y),
            n_clusters=n_clusters,
            init=init,
            max_iter=max_iter,
            tol=tol,
            random_state=random_state,
            checkpoint_every=checkpoint_every,
            checkpoint_dir=checkpoint_dir,
            resume_from=resume_from,
        )

    def fit(self, x: DNDarray) -> "KMedians":
        """Iterate until median shift < tol (kmedians.py:~120)."""
        if not isinstance(x, DNDarray):
            raise ValueError(f"input needs to be a DNDarray, but was {type(x)}")
        if x.ndim != 2:
            raise ValueError(f"input needs to be 2D, but was {x.ndim}D")
        # Host spans at the fit's phases; every launch below is asynchronous,
        # so a span times the host's part (init, enqueue, wrap), not the device.
        with _span("ht.cluster.KMedians.fit", rows=x.shape[0], features=x.shape[1],
                   clusters=self.n_clusters, max_iter=self.max_iter) as root:
            root.attrs.update(passes=self._fit(x))
        return self

    def _fit(self, x: DNDarray) -> int:
        """The fit; returns the reads of the points an iteration makes."""
        n, k, tol = x.shape[0], self.n_clusters, float(self.tol)
        # rows as they lie, padding and all; columns split (rare) as the true-shape array
        xp = x.larray_padded if x.split == 0 else x._dense()
        if not types.heat_type_is_inexact(x.dtype):
            xp = xp.astype(jnp.float32)
        dtype = xp.dtype
        # of a turn's reads of the points, the selection's are calls of a kernel, every one
        plan = dict(bits=statistics._GROUP_BITS, passes=passes_an_iteration(dtype, k),
                    kernel_passes=statistics._select_passes(dtype, k, True, grouped=True))
        on = dict(n_true=n, comm=x.comm if x.split == 0 and x.comm.size > 1 else None)

        def init_centers():
            with _span("kmedians.init"):
                self._initialize_cluster_centers(x)
            return self._cluster_centers._dense().astype(dtype)

        if self._resumable:
            # chunked checkpoint/resume path: the SAME iteration body as the
            # fast path, `checkpoint_every` iterations a device program,
            # centers checkpointed (and divergence-guarded) between chunks
            def run_chunk(centers, chunk):
                dispatch.record_external_dispatch()
                return _kmedians_loop(xp, jnp.asarray(centers, dtype), max_iter=chunk, tol=tol, **on)

            with _span("kmedians.loop", **plan):
                new, n_iter = self._run_resumable(run_chunk, init_centers, "kmedians.iter")
                new = jnp.asarray(new, dtype)
        else:
            centers = init_centers()
            # the whole loop is ONE launch, and the iteration count stays a
            # device scalar: fit() reads nothing back; n_iter_ and inertia_
            # convert on first access
            with _span("kmedians.loop", **plan):
                dispatch.record_external_dispatch()
                new, n_iter, _ = _kmedians_loop(xp, centers, max_iter=self.max_iter, tol=tol, **on)
        self._cluster_centers = DNDarray.from_dense(new, None, x.device, x.comm)
        self._n_iter = n_iter
        # final assignment against the last centers: labels and inertia from
        # the loop's own assignment, one more read of the points
        with _span("kmedians.assign"):
            dispatch.record_external_dispatch()
            labels, self._inertia = _kmedians_assign(xp, new, **on)
            self._labels = DNDarray.from_dense(labels[:n], 0 if x.split == 0 else None, x.device, x.comm)
        return plan["passes"]
