"""Shared k-clustering base, analog of heat/cluster/_kcluster.py.

``_KCluster`` (_kcluster.py:10) holds the iteration loop and the two
initializations: random sampling and kmeans++ (``probability_based``,
_kcluster.py:97-207).  All distributed behavior rides on the ops layer
(cdist + argmin + masked reductions over the sharded sample axis).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp

_jit_partial = functools.partial(jax.jit, static_argnames=("k",))

from ..core import types
from ..core.base import BaseEstimator, ClusteringMixin, lazy_scalar_property
from ..core.dndarray import DNDarray

__all__ = ["_KCluster"]


@_jit_partial
def _kmeanspp_init(dense: jax.Array, first_idx: jax.Array, u_all: jax.Array, k: int) -> jax.Array:
    """Greedy D^2-weighted kmeans++ seeding as one compiled program.

    ``u_all`` holds the k-1 pre-drawn uniforms (one per added center), so
    the library RNG stream is consumed outside and the loop is pure.
    """
    n, f = dense.shape
    x2 = jnp.sum(dense * dense, axis=1)
    centers0 = jnp.zeros((k, f), dense.dtype).at[0].set(dense[first_idx])

    def body(i, centers):
        c2 = jnp.sum(centers * centers, axis=1)
        d_all = x2[:, None] + c2[None, :] - 2.0 * (dense @ centers.T)
        d_all = d_all + jnp.where(jnp.arange(k)[None, :] >= i, jnp.inf, 0.0)
        d2 = jnp.maximum(jnp.min(d_all, axis=1), 0.0)
        probs = d2 / jnp.maximum(jnp.sum(d2), 1e-30)
        u = u_all[i - 1]
        next_idx = jnp.clip(jnp.searchsorted(jnp.cumsum(probs), u), 0, n - 1)
        return centers.at[i].set(dense[next_idx])

    return jax.lax.fori_loop(1, k, body, centers0)


class _KCluster(BaseEstimator, ClusteringMixin):
    """Base class for k-statistics clustering (_kcluster.py:10).

    ``checkpoint_every=N`` + ``checkpoint_dir`` make the fit resumable:
    every N iterations the centers are checkpointed through the
    filesystem-native :class:`~heat_tpu.utils.checkpoint.Checkpointer`,
    and ``resume_from=dir`` continues a killed fit from its last
    checkpoint, reproducing the uninterrupted result exactly (the
    chunked loop runs the identical iteration sequence).  The chunked
    path also guards against NaN/Inf divergence
    (:class:`~heat_tpu.resilience.DivergenceError` carrying the last
    finite centers)."""

    def __init__(
        self,
        metric: Callable,
        n_clusters: int,
        init: Union[str, DNDarray],
        max_iter: int,
        tol: float,
        random_state: Optional[int],
        checkpoint_every: Optional[int] = None,
        checkpoint_dir: Optional[str] = None,
        resume_from: Optional[str] = None,
    ):
        from ..core.base import validate_resume_params

        validate_resume_params(checkpoint_every, checkpoint_dir, resume_from)
        self.n_clusters = n_clusters
        self.init = init
        self.max_iter = max_iter
        self.tol = tol
        self.random_state = random_state
        self.checkpoint_every = checkpoint_every
        self.checkpoint_dir = checkpoint_dir
        self.resume_from = resume_from

        self._metric = metric
        self._cluster_centers = None
        self._labels = None
        self._inertia = None
        self._n_iter = None

    @property
    def _resumable(self) -> bool:
        """Whether the fit must take the chunked checkpoint/resume path."""
        return self.checkpoint_every is not None or self.resume_from is not None

    @property
    def cluster_centers_(self) -> DNDarray:
        return self._cluster_centers

    @property
    def labels_(self) -> DNDarray:
        return self._labels

    # fits store device scalars so fit() never blocks on a device->host sync; the
    # host conversion happens (once) on first access
    inertia_ = lazy_scalar_property("_inertia", float)
    n_iter_ = lazy_scalar_property("_n_iter", int)

    def _initialize_cluster_centers(self, x: DNDarray, oversampling: float = None, iter_multiplier: float = None):
        """Random / kmeans++ / explicit initialization (_kcluster.py:97)."""
        if self.random_state is not None:
            from ..core import random as ht_random

            ht_random.seed(self.random_state)
        from ..core import random as ht_random

        n, f = x.shape
        k = self.n_clusters
        exact = types.heat_type_is_inexact(x.dtype)

        def points():
            # only where points are drawn: the true-shape view of a padded array is a copy of it
            return x._dense() if exact else x._dense().astype(jnp.float32)

        if isinstance(self.init, DNDarray):
            if self.init.shape != (k, f):
                raise ValueError(f"passed centroids need to be of shape ({k}, {f}), but are {self.init.shape}")
            centers = self.init._dense().astype(x.larray_padded.dtype if exact else jnp.float32)
        elif self.init == "random":
            dense = points()
            # k DISTINCT data points (argsort of one uniform draw = a
            # random sample without replacement).  Sampling indices WITH
            # replacement could seed two centers on the same point — a
            # state the median/medoid update can never leave (their
            # clusters tie forever), and which cost the KMedians/
            # KMedoids blob fits a whole blob at unlucky seeds
            u = ht_random.rand(n, comm=x.comm)._dense()
            idx = jnp.argsort(u)[:k]
            centers = dense[idx]
        elif self.init in ("kmeans++", "probability_based", "++"):
            dense = points()
            # kmeans++ sampling (_kcluster.py:112-180): greedy D^2 weighting.
            # The uniforms are pre-drawn one call per added center — the
            # exact draw sequence of the release before the loop was fused,
            # so seeded results are stable — then the greedy loop compiles
            # as one program: centers preallocated at (k, f) with unfilled
            # slots masked to +inf so every round has identical shapes.
            key_arr = ht_random.randint(0, n, size=(1,), comm=x.comm)._dense()
            if k > 1:
                u_all = jnp.concatenate(
                    [ht_random.rand(1, comm=x.comm)._dense() for _ in range(k - 1)]
                )
            else:
                u_all = jnp.zeros((1,), jnp.float32)
            centers = _kmeanspp_init(dense, key_arr[0], u_all, k)
        elif self.init == "batchparallel":
            raise NotImplementedError("batchparallel init: use BatchParallelKMeans")
        else:
            raise ValueError(
                f'init needs to be one of "random", ht.DNDarray or "kmeans++", but was {self.init}'
            )
        self._cluster_centers = DNDarray.from_dense(centers, None, x.device, x.comm)

    def _assign_to_cluster(self, x: DNDarray, eval_functional_value: bool = False):
        """Label each sample with its nearest center (_kcluster.py:208)."""
        distances = self._metric(x, self._cluster_centers)
        from ..core import statistics

        labels = statistics.argmin(distances, axis=1)
        if eval_functional_value:
            from ..core import arithmetics

            # stays a lazy 0-d value; inertia_ converts on first access
            self._inertia = arithmetics.sum(statistics.min(distances, axis=1) ** 2)._dense()
        return labels

    def _run_resumable(self, run_chunk, init_centers, site: str):
        """Chunked checkpoint/resume driver around the jitted fit loop
        (see :func:`heat_tpu.core.base.resumable_fit_loop`)."""
        from ..core.base import resumable_fit_loop

        return resumable_fit_loop(
            run_chunk,
            init_centers,
            self.max_iter,
            float(self.tol),
            checkpoint_every=self.checkpoint_every,
            checkpoint_dir=self.checkpoint_dir,
            resume_from=self.resume_from,
            site=site,
            what="cluster centers",
        )

    def fit(self, x: DNDarray):
        raise NotImplementedError()

    def predict(self, x: DNDarray) -> DNDarray:
        """Nearest learned center for each sample (_kcluster.py:268).

        Runs under this kind's precision-policy scope
        (:mod:`heat_tpu.analysis.precision_policy`): the dispatch
        analyze hook checks the compiled program against the declared
        policy, and a ``tolerance`` policy + ``HEAT_TPU_PREDICT_DTYPE``
        flips the cdist cross term to bf16 compute (KMeans; the
        ``bitwise`` kinds always serve native f32)."""
        if not isinstance(x, DNDarray):
            raise ValueError(f"input needs to be a DNDarray, but was {type(x)}")
        from ..analysis import precision_policy as _pp

        with _pp.scope(type(self).__name__):
            return self._assign_to_cluster(x)
