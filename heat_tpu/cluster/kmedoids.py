"""KMedoids clustering, analog of heat/cluster/kmedoids.py (kmedoids.py:11).

Centers snap to the closest actual data point (medoid) after a
KMeans-style mean update, matching the reference's variant.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp

from ..core import types
from ..core.dndarray import DNDarray
from ..spatial import distance
from ._kcluster import _KCluster

__all__ = ["KMedoids"]


@partial(jax.jit, static_argnames=("k", "max_iter"))
def _kmedoids_loop(dense: jax.Array, centers: jax.Array, k: int, max_iter: int):
    """Whole KMedoids fit as one on-device while_loop (medoids are data
    points, so the stop test is exact zero movement)."""

    def update(c):
        d = jnp.sum(jnp.abs(dense[:, None, :] - c[None, :, :]), axis=-1)
        labels = jnp.argmin(d, axis=1)
        new_rows = []
        for j in range(k):
            mask = labels == j
            cnt = jnp.sum(mask)
            mean = jnp.where(
                cnt > 0,
                jnp.sum(jnp.where(mask[:, None], dense, 0.0), axis=0) / jnp.maximum(cnt, 1),
                c[j],
            )
            dm = jnp.sum(jnp.abs(dense - mean[None, :]), axis=1)
            dm_in = jnp.where(mask, dm, jnp.inf)
            dm = jnp.where(cnt > 0, dm_in, dm)
            new_rows.append(dense[jnp.argmin(dm)])
        return jnp.stack(new_rows)

    def cond(carry):
        c, i, shift = carry
        return jnp.logical_and(i < max_iter, shift > 0.0)

    def body(carry):
        c, i, _ = carry
        new = update(c)
        shift = jnp.sum(jnp.abs(new - c)).astype(jnp.float32)
        return new, i + 1, shift

    init = (centers, jnp.int32(0), jnp.asarray(jnp.inf, jnp.float32))
    c, i, shift = jax.lax.while_loop(cond, body, init)
    return c, i, shift


class KMedoids(_KCluster):
    """Manhattan-metric k-medoids (kmedoids.py:11)."""

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, DNDarray] = "random",
        max_iter: int = 300,
        random_state: Optional[int] = None,
        checkpoint_every: Optional[int] = None,
        checkpoint_dir: Optional[str] = None,
        resume_from: Optional[str] = None,
    ):
        if isinstance(init, str) and init == "kmedoids++":
            init = "probability_based"
        super().__init__(
            metric=lambda x, y: distance.manhattan(x, y),
            n_clusters=n_clusters,
            init=init,
            max_iter=max_iter,
            tol=0.0,
            random_state=random_state,
            checkpoint_every=checkpoint_every,
            checkpoint_dir=checkpoint_dir,
            resume_from=resume_from,
        )

    def fit(self, x: DNDarray) -> "KMedoids":
        """Iterate until the medoids stop moving (kmedoids.py:~110)."""
        if not isinstance(x, DNDarray):
            raise ValueError(f"input needs to be a DNDarray, but was {type(x)}")
        if x.ndim != 2:
            raise ValueError(f"input needs to be 2D, but was {x.ndim}D")
        dense = x._dense()
        if not types.heat_type_is_inexact(x.dtype):
            dense = dense.astype(jnp.float32)
        if self._resumable:
            dtype = dense.dtype

            def run_chunk(centers, n):
                return _kmedoids_loop(dense, jnp.asarray(centers, dtype), self.n_clusters, n)

            def init_centers():
                self._initialize_cluster_centers(x)
                return self._cluster_centers._dense().astype(dtype)

            new, n_iter = self._run_resumable(run_chunk, init_centers, "kmedoids.iter")
            new = jnp.asarray(new, dtype)
        else:
            self._initialize_cluster_centers(x)
            centers = self._cluster_centers._dense().astype(dense.dtype)
            new, n_iter, _ = _kmedoids_loop(dense, centers, self.n_clusters, self.max_iter)
        self._cluster_centers = DNDarray.from_dense(new, None, x.device, x.comm)
        self._n_iter = n_iter  # lazy host conversion in n_iter_
        self._labels = self._assign_to_cluster(x, eval_functional_value=True)
        return self
