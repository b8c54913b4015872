"""Spectral clustering, analog of heat/cluster/spectral.py (spectral.py:12).

Pipeline (matching the reference): similarity -> graph Laplacian ->
Lanczos eigen-embedding -> KMeans on the leading eigenvectors.
"""

from __future__ import annotations

import functools
from typing import Optional, Union

import jax.numpy as jnp

from ..core import types
from ..core.base import BaseEstimator, ClusteringMixin
from ..core.dndarray import DNDarray
from ..graph import Laplacian
from ..spatial import distance
from .kmeans import KMeans

__all__ = ["Spectral"]


def _make_similarity(metric: str, gamma: float):
    if metric == "rbf":
        sigma = float(jnp.sqrt(1.0 / (2.0 * gamma)))
        return lambda x: distance.rbf(x, sigma=sigma)
    if metric == "euclidean":
        # expanded form: one MXU matmul instead of an O(n^2 f) VPU reduce
        return lambda x: distance.cdist(x, quadratic_expansion=True)
    raise NotImplementedError(
        f"Other kernels than rbf and euclidean are currently not supported, got {metric!r}"
    )


@functools.lru_cache(maxsize=32)
def _embed_fn(metric: str, gamma: float, mode: str, boundary: str, threshold: float):
    """Fused spectral-embedding program, cached per Laplacian config so
    every Spectral instance with the same settings reuses one compilation
    (an instance-level cache would recompile on every fresh estimator)."""
    from ..core import fusion
    from ..core.linalg import solver

    laplacian = Laplacian(
        _make_similarity(metric, gamma), definition="norm_sym", mode=mode,
        threshold_key=boundary, threshold_value=threshold,
    )

    @fusion.jit
    def embed(xx, vv, m):
        L = laplacian.construct(xx)
        vd = vv._dense()
        vn = vd / jnp.linalg.norm(vd)
        V, T = solver.lanczos(L, m, v0=DNDarray.from_dense(vn, None, xx.device, xx.comm))
        evals, evecs_T = jnp.linalg.eigh(T._dense())
        # eigenvectors of L approx V @ eigenvectors(T)
        embedding = V._dense() @ evecs_T
        return evals, DNDarray.from_dense(embedding, xx.split, xx.device, xx.comm)

    return embed


class Spectral(BaseEstimator, ClusteringMixin):
    """Spectral clustering on a similarity graph (spectral.py:12)."""

    def __init__(
        self,
        n_clusters: Optional[int] = None,
        gamma: float = 1.0,
        metric: str = "rbf",
        laplacian: str = "fully_connected",
        threshold: float = 1.0,
        boundary: str = "upper",
        n_lanczos: int = 300,
        assign_labels: str = "kmeans",
        **params,
    ):
        self.n_clusters = n_clusters
        self.gamma = gamma
        self.metric = metric
        self.laplacian = laplacian
        self.threshold = threshold
        self.boundary = boundary
        self.n_lanczos = n_lanczos
        self.assign_labels = assign_labels

        # kept for API parity / introspection only — the fit path goes
        # through _embed_fn, which derives an IDENTICAL Laplacian from the
        # same (metric, gamma, mode, boundary, threshold) config so fused
        # compilations are shared across estimator instances
        self._laplacian = Laplacian(
            _make_similarity(metric, gamma), definition="norm_sym", mode=laplacian,
            threshold_key=boundary, threshold_value=threshold,
        )
        if assign_labels == "kmeans":
            self._cluster = KMeans(n_clusters=n_clusters, init="kmeans++") if n_clusters else KMeans(init="kmeans++")
        else:
            raise NotImplementedError(f"Other clustering methods than kmeans are currently not supported, got {assign_labels!r}")
        self._labels = None
        self._eigenvectors = None

    @property
    def labels_(self) -> DNDarray:
        return self._labels

    def _spectral_embedding(self, x: DNDarray):
        """Laplacian + Lanczos eigendecomposition (spectral.py:120+).

        The whole pipeline (similarity, Laplacian, Krylov loop, small
        eigh, embedding matmul) runs as ONE ht.jit program — dispatched
        eagerly it is ~20 ops, each its own host dispatch.
        The Lanczos start vector is drawn OUTSIDE the trace so the library
        RNG stream advances per fit instead of being baked into the cache.
        """
        from ..core import random as ht_random

        n = x.shape[0]
        m = min(self.n_lanczos, n)
        v0 = ht_random.randn(n, comm=x.comm)
        embed = _embed_fn(
            self.metric, float(self.gamma), self.laplacian, self.boundary, float(self.threshold)
        )
        return embed(x, v0, m)

    def fit(self, x: DNDarray) -> "Spectral":
        """Embed and cluster (spectral.py:172)."""
        if not isinstance(x, DNDarray):
            raise ValueError(f"input needs to be a DNDarray, but was {type(x)}")
        if x.ndim != 2:
            raise ValueError(f"input needs to be 2D, but was {x.ndim}D")
        evals, evecs = self._spectral_embedding(x)

        if self.n_clusters is None:
            # eigengap heuristic (spectral.py:190)
            diffs = jnp.diff(evals)
            self.n_clusters = int(jnp.argmax(diffs[: min(50, diffs.shape[0])])) + 1
            self._cluster.n_clusters = self.n_clusters

        components = DNDarray.from_dense(
            evecs._dense()[:, : self.n_clusters], x.split, x.device, x.comm
        )
        self._cluster.fit(components)
        self._labels = self._cluster.labels_
        self._eigenvectors = evecs
        return self

    def predict(self, x: DNDarray) -> DNDarray:
        """Labels for the fitted data (spectral.py:230; like the reference,
        prediction is only defined on the training data)."""
        return self._labels
