"""KMeans clustering, analog of heat/cluster/kmeans.py (kmeans.py:14).

The centroid update — a one-hot masked matmul + sum in the reference,
followed by an Allreduce across the sample-split axis — is one product of
the one-hot against the sharded global array (`_cluster_means`); XLA emits
the psum.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp

from ..core import dispatch, types
from ..core.dndarray import DNDarray
from ..spatial import distance
from ..telemetry.spans import span as _span
from ._kcluster import _KCluster

__all__ = ["KMeans"]


@partial(jax.jit, static_argnames=("n_true", "k", "max_iter", "tol"))
def _lloyd_loop(xp: jax.Array, centers: jax.Array, n_true: int, k: int, max_iter: int, tol: float):
    """The whole Lloyd fit loop as one on-device ``lax.while_loop``.

    A Python loop checking ``float(shift) <= tol`` costs one device->host
    round trip per iteration; here
    the convergence test runs on-device and the host syncs exactly once,
    after the loop.  An iteration needs no inertia and no ``|x|^2``, so it is
    the assignment (the distance product, the argmin and its minimum) and the
    update (the one-hot against the points and a column of ones), over a
    bfloat16 copy of the points made once, before the loop: two passes over
    the copy and nothing else of that length (`tests/test_chip_compile.py`
    pins them; 7.65 + 4.77 ms at 10^8 x 16, 8 clusters, PERF.md, PR 28),
    three where rows are padded and the row mask is written.  Labels and
    inertia come from one final `_lloyd_step`.  Returns (centers, n_iter,
    last_shift).
    """

    def cond(carry):
        c, i, shift = carry
        return jnp.logical_and(i < max_iter, shift > tol)

    def body(carry):
        c, i, _ = carry
        new, shift = _lloyd_body(xp, c, n_true, k)
        return new, i + 1, shift

    init = (centers, jnp.int32(0), jnp.asarray(jnp.inf, jnp.float32))
    c, i, shift = jax.lax.while_loop(cond, body, init)
    return c, i, shift


def _half_d2(xb, centers):
    """``|c|^2 - 2 x.c`` for bfloat16 points ``xb``: the squared distance of
    every row to every center, less the row's ``|x|^2``, which cannot change
    the argmin.

    The arithmetic is written here and not left to the backend's default (a
    float32 product rounds both operands to bfloat16 on the MXU and neither on
    the CPU).  The points enter rounded to bfloat16: one stream of half the
    bytes, and a rounding that falls on either side of every boundary alike.
    The centers enter in float32, in ``|c|^2`` and in the cross term, whose
    product asks ``HIGH`` on their side (on the MXU several bfloat16 terms of
    the centers against the one stream of points: measured as near float64 as
    a float32 product at ``HIGHEST``; exact on the CPU): both terms come from
    the SAME centers.  With the centers rounded to bfloat16 in the cross term
    alone, or in both terms, every boundary lies off by some
    ``x . (c - bf16(c))``, and 30 iterations over 10^8 overlapping points end
    4e-3 to 1e-2 from the exact fit's centers, 40 times this form's distance
    (PERF.md, PR 27)."""
    c = centers.astype(jnp.float32)
    xc = jax.lax.dot_general(
        xb, c, (((1,), (1,)), ((), ())),
        precision=(jax.lax.Precision.DEFAULT, jax.lax.Precision.HIGH),
        preferred_element_type=jnp.float32,
    )
    return jnp.sum(c * c, axis=1)[None, :] - 2.0 * xc


def _real_rows(n, n_true):
    """Which of ``n`` padded rows are real: the first ``n_true``."""
    return jax.lax.broadcasted_iota(jnp.int32, (n,), 0) < n_true


def _cluster_means(xb, labels, centers, n_true, k, resident):
    """Per-cluster means of the bfloat16 points ``xb``, an empty cluster
    keeping its center; returns (new centers, the squared shift).  The sums
    are the product of the exact bfloat16 one-hot against the rounded points,
    summed in float32 (the operands the MXU's default gave the update).  Where
    the counts come from is a static fact of the caller:

    * ``resident`` (the fit loop, which holds ``xb`` as a copy in memory): a
      column of ones appended to the points, so ONE product reads the copy
      and the labels once and its last column is the counts, sums of exact
      1 x 1 terms.  The compiler fuses the column into the product, and no
      pass sums the one-hot a second time (1.1 ms an iteration at 10^8 x 16).
    * not ``resident`` (one iteration alone, `_lloyd_step`, where the
      compiler fuses the convert to bfloat16 into each product and holds no
      copy): a column between the convert and the product makes it write the
      copy, 3.2 GB and 5.7 ms more at 10^8 x 16.
      There the counts are the one-hot's own sum, a pass over the labels (the
      one-hot against itself as a third product reads 3.4 ms for that 1.1,
      PERF.md, PR 28).

    Either is exact in float32 while a cluster holds under 2^24 rows a chip.
    The row mask exists only where rows are padded, another static fact; pad
    rows are not guaranteed zero, so there the one-hot is masked."""
    n, f = xb.shape
    oh = jax.nn.one_hot(labels, k, dtype=xb.dtype)
    if n != n_true:
        oh = oh * _real_rows(n, n_true).astype(xb.dtype)[:, None]
    over_rows = (((0,), (0,)), ((), ()))
    if resident:
        x1 = jnp.concatenate([xb, jnp.ones((n, 1), xb.dtype)], axis=1)
        sums1 = jax.lax.dot_general(oh, x1, over_rows, preferred_element_type=jnp.float32)
        sums, counts = sums1[:, :f], sums1[:, f]
    else:
        sums = jax.lax.dot_general(oh, xb, over_rows, preferred_element_type=jnp.float32)
        counts = jnp.sum(oh, axis=0, dtype=jnp.float32)
    means = (sums / jnp.maximum(counts, 1.0)[:, None]).astype(centers.dtype)
    new = jnp.where(counts[:, None] > 0, means, centers)
    return new, jnp.sum((new - centers) ** 2)


def _lloyd_body(xp, centers, n_true, k):
    # does not change in the loop: the compiler makes the copy once, before it
    xb = xp.astype(jnp.bfloat16)
    # the scopes name the two passes in the device trace; metadata only
    with jax.named_scope("lloyd.assign"):
        labels = jnp.argmin(_half_d2(xb, centers), axis=1)
    with jax.named_scope("lloyd.update"):
        new, shift = _cluster_means(xb, labels, centers, n_true, k, resident=True)
    return new, shift.astype(jnp.float32)


@partial(jax.jit, static_argnames=("n_true", "k"))
def _lloyd_step(xp: jax.Array, centers: jax.Array, n_true: int, k: int):
    """One fused Lloyd iteration on the padded sharded array.

    The reference runs this as three separate distributed ops (ring cdist
    distance.py:209, argmin with a custom MPI op statistics.py:1372, one-hot
    matmul + Allreduce kmeans.py:80-120).  Fusing into one jitted program
    keeps the whole iteration on-device: assignment needs only
    ``|c|^2 - 2 x@c.T`` (the ``|x|^2`` row term cannot change the argmin),
    both matmuls ride the MXU, and under a sharded ``xp`` GSPMD turns the
    segment sums into a single psum over the sample axis.  Alone, it holds
    no bfloat16 copy of the points: the compiler fuses the convert into each
    product, so the update takes its counts from the one-hot's own sum
    (`_cluster_means`, not ``resident``, has why).

    Returns (labels_padded, new_centers, shift, inertia).
    """
    xb = xp.astype(jnp.bfloat16)
    with jax.named_scope("lloyd.assign"):
        half_d2 = _half_d2(xb, centers)  # (N, k) — MXU; squared distance minus |x|^2 row term
        labels = jnp.argmin(half_d2, axis=1)
    with jax.named_scope("lloyd.update"):  # GSPMD: the sums' psum across shards
        new, shift = _cluster_means(xb, labels, centers, n_true, k, resident=False)
    with jax.named_scope("lloyd.assign"):  # the inertia is the assignment's own sum
        d2 = jnp.sum(xp * xp, axis=1) + jnp.min(half_d2, axis=1)
        if xp.shape[0] != n_true:
            d2 = _real_rows(xp.shape[0], n_true).astype(xp.dtype) * d2
        inertia = jnp.sum(d2)
    return labels, new, shift, inertia


class KMeans(_KCluster):
    """K-Means with Lloyd iterations (kmeans.py:14)."""

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
        if max_iter < 1:
            raise ValueError(f"max_iter must be >= 1, got {max_iter}")
        if isinstance(init, str) and init == "kmeans++":
            init = "probability_based"
        super().__init__(
            metric=lambda x, y: distance.cdist(x, y, quadratic_expansion=True),
            n_clusters=n_clusters,
            init=init,
            max_iter=max_iter,
            tol=tol,
            random_state=random_state,
            checkpoint_every=checkpoint_every,
            checkpoint_dir=checkpoint_dir,
            resume_from=resume_from,
        )

    def _account_lloyd_psum(self, x: DNDarray, xp):
        """Telemetry model of the GSPMD psum behind one launched Lloyd
        program: the per-cluster partial sums (k, f) plus counts (k,)
        reduced across the sample-split shards (`sums = oh.T @ xp` —
        XLA inserts the collective; this layer never issues it, so the
        comm accounting happens here at launch).  Returns a ``comm.psum``
        span to wrap the launch with; a no-op for replicated input."""
        if x.split is None or x.comm.size <= 1:
            return contextlib.nullcontext()
        k = self.n_clusters
        nbytes = (k * int(xp.shape[1]) + k) * xp.dtype.itemsize
        return x.comm.account_implicit("psum", nbytes, site="kmeans.lloyd")

    def _assign_padded(self, x: DNDarray):
        """Labels + inertia against the current centers (one cheap pass)."""
        xp = x.larray_padded
        if not types.heat_type_is_inexact(x.dtype):
            xp = xp.astype(jnp.float32)
        centers = self._cluster_centers._dense().astype(xp.dtype)
        dispatch.record_external_dispatch()
        with self._account_lloyd_psum(x, xp):
            labels, _, _, inertia = _lloyd_step(xp, centers, x.shape[0], self.n_clusters)
        return labels, inertia

    def fit(self, x: DNDarray) -> "KMeans":
        """Lloyd iterations until center shift < tol (kmeans.py:~100)."""
        if not isinstance(x, DNDarray):
            raise ValueError(f"input needs to be a DNDarray, but was {type(x)}")
        if x.ndim != 2:
            raise ValueError(f"input needs to be 2D, but was {x.ndim}D")
        # Host spans at the fit's phases; every launch below is asynchronous,
        # so a span times the host's part (init, enqueue, wrap), not the device.
        with _span("ht.cluster.KMeans.fit", rows=x.shape[0], features=x.shape[1],
                   clusters=self.n_clusters, max_iter=self.max_iter):
            self._fit(x)
        return self

    def _fit(self, x: DNDarray) -> None:
        xp = x.larray_padded
        if not types.heat_type_is_inexact(x.dtype):
            xp = xp.astype(jnp.float32)
        dtype = xp.dtype

        def init_centers():
            with _span("kmeans.init"):
                self._initialize_cluster_centers(x)
            return self._cluster_centers._dense().astype(dtype)

        if self._resumable:
            # chunked checkpoint/resume path: the SAME `_lloyd_body`
            # iteration sequence as the fast path, run checkpoint_every
            # iterations per device program, centers checkpointed (and
            # divergence-guarded) between chunks.  A killed fit resumed
            # from its last checkpoint reproduces the uninterrupted
            # result exactly.  (`kmeans.init` nests in `kmeans.loop` here:
            # a resumed fit does not initialize.)
            def run_chunk(centers, n):
                dispatch.record_external_dispatch()
                with self._account_lloyd_psum(x, xp):
                    return _lloyd_loop(
                        xp, jnp.asarray(centers, dtype), x.shape[0],
                        self.n_clusters, n, float(self.tol),
                    )

            with _span("kmeans.loop"):
                centers, n_iter = self._run_resumable(run_chunk, init_centers, "kmeans.iter")
                self._cluster_centers = DNDarray.from_dense(
                    jnp.asarray(centers, dtype), None, x.device, x.comm
                )
        else:
            centers = init_centers()
            # whole fit loop on-device, and the iteration count stays a
            # device scalar — fit() performs ZERO host syncs; n_iter_ and
            # inertia_ convert lazily on first access (one device->host
            # sync each, paid only if the caller looks).  ONE
            # dispatch for the whole fit, however many Lloyd iterations —
            # the dispatch-amortization invariant the micro-test pins.
            with _span("kmeans.loop"):
                dispatch.record_external_dispatch()
                with self._account_lloyd_psum(x, xp):
                    new, n_iter, _ = _lloyd_loop(
                        xp, centers, x.shape[0], self.n_clusters, self.max_iter, float(self.tol)
                    )
                self._cluster_centers = DNDarray.from_dense(new, None, x.device, x.comm)

        self._n_iter = n_iter
        # final assignment against the converged centers (the reference's
        # last pass only assigns, it does not move centers)
        with _span("kmeans.assign"):
            labels, self._inertia = self._assign_padded(x)
            self._labels = DNDarray.from_dense(labels[: x.shape[0]], x.split, x.device, x.comm)
