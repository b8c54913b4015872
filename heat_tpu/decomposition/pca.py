"""Principal component analysis, analog of heat/decomposition/pca.py
(pca.py:19-496).

svd_solver options match the reference: 'full' (tall-skinny exact SVD),
'hierarchical' (hsvd_rank / hsvd_rtol) and 'randomized' (the steps of rsvd,
taken about the column means as each product reads the table).
"""

from __future__ import annotations

import sys
from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dispatch, types
from ..core.base import BaseEstimator, TransformMixin, lazy_scalar_property, validate_resume_params
from ..core.dndarray import DNDarray
from ..core.linalg.svd import svd as _exact_svd
from ..core.linalg import svdtools
from ..telemetry.spans import span as _span

__all__ = ["PCA"]

#: checkpoint step ids of the two fit stages (directory-per-step layout)
_STAGE_MEAN = 0
_STAGE_FITTED = 1


@partial(jax.jit, static_argnames=("n", "k", "ell", "power_iter"))
def _randomized_program(x, mean, var, key, *, n: int, k: int, ell: int, power_iter: int):
    """The randomized solver after the moments, ONE program: the sketch
    ``omega`` (``features x ell`` standard normals from ``key``, what
    ``ht.random.randn`` draws from it), the factorization of ``x - mean``
    (:func:`svdtools._centered_rsvd`: the centring inside each product's
    read of ``x``, no ``U``) and everything a user reads of the fit:
    ``(components, singular values, explained variance, its ratio to the
    total, which is the sum of ``var``, and the ratios' sum)``.  ``x`` comes
    as it lies; rows from ``n`` on are padding and count for nothing."""
    real = None if x.shape[0] == n else (jnp.arange(x.shape[0]) < n).astype(mean.dtype)
    omega = jax.random.normal(key, (x.shape[1], ell), mean.dtype)
    _, s, vt = svdtools._centered_rsvd(x, mean, omega, power_iter, k, real)
    ev = s * s / max(n - 1, 1)
    ratio = ev / jnp.maximum(jnp.sum(var), 1e-30)
    return vt, s, ev, ratio, jnp.sum(ratio)


def _randomized_fit(x, mean, var, key, **plan):
    """The randomized solver's one launch; looked up by name where ``fit``
    calls it."""
    return _randomized_program(x, mean, var, key, **plan)


class PCA(BaseEstimator, TransformMixin):
    """Linear dimensionality reduction via SVD of centered data (pca.py:19).

    The randomized solver departs from scikit-learn's in two places.
    ``iterated_power="auto"`` means 0 power iterations here, as in the
    reference, where scikit-learn's rule takes 7 (``n_components`` under a
    tenth of the smaller extent) or 4: on data whose spectrum has a gap after
    the components asked for, 0 leaves the singular values some 1e-3 off and
    1 leaves them at float32 rounding, so pass ``iterated_power`` where
    accuracy matters.  And every sketch is orthonormalized by
    ``svdtools._gram_orthonormalize`` (the eigendecomposition of its Gram,
    twice, which the chip's matrix unit runs), not by QR:
    ``power_iteration_normalizer`` is accepted and not used."""

    def __init__(
        self,
        n_components: Optional[Union[int, float]] = None,
        copy: bool = True,
        whiten: bool = False,
        svd_solver: str = "hierarchical",
        tol: Optional[float] = None,
        iterated_power: Union[str, int] = "auto",
        n_oversamples: int = 10,
        power_iteration_normalizer: str = "qr",
        random_state: Optional[int] = None,
        checkpoint_every: Optional[int] = None,
        checkpoint_dir: Optional[str] = None,
        resume_from: Optional[str] = None,
    ):
        if whiten:
            raise NotImplementedError("whitening is not yet supported (matching pca.py:135)")
        if svd_solver not in ("full", "hierarchical", "randomized"):
            raise ValueError(f"svd_solver must be 'full', 'hierarchical' or 'randomized', got {svd_solver!r}")
        if random_state is not None and not isinstance(random_state, int):
            raise ValueError(f"random_state must be None or int, got {type(random_state)}")
        validate_resume_params(checkpoint_every, checkpoint_dir, resume_from)
        # PCA's fit is staged (mean -> solver) rather than iterated;
        # checkpoint_every acts as the enable flag for stage checkpoints
        self.checkpoint_every = checkpoint_every
        self.checkpoint_dir = checkpoint_dir
        self.resume_from = resume_from

        self.n_components = n_components
        self.copy = copy
        self.whiten = whiten
        self.svd_solver = svd_solver
        self.tol = tol
        self.iterated_power = iterated_power
        self.n_oversamples = n_oversamples
        self.power_iteration_normalizer = power_iteration_normalizer
        self.random_state = random_state

        self.components_ = None
        self.explained_variance_ = None
        self.explained_variance_ratio_ = None
        self.singular_values_ = None
        self.mean_ = None
        self.n_components_ = None
        self._tevr = None
        self.noise_variance_ = None

    # fits store a lazy device scalar (no host sync inside fit); the
    # conversion happens once on first access
    total_explained_variance_ratio_ = lazy_scalar_property("_tevr", float)

    def _checkpointer(self, for_write: bool):
        directory = self.checkpoint_dir or self.resume_from
        if directory is None or (for_write and self.checkpoint_every is None):
            return None
        from ..utils.checkpoint import Checkpointer
        from ..utils.overlap import async_checkpoint_enabled

        ck = Checkpointer(directory)
        if for_write and async_checkpoint_enabled():
            # stage writes run on the overlap layer's background writer:
            # the mean-stage checkpoint overlaps the SVD solve, and fit()
            # drains the writer before returning or re-raising
            return ck.as_async()
        return ck

    def _restore_fitted(self, saved: dict, X: DNDarray) -> None:
        as_dnd = lambda a: DNDarray.from_dense(jnp.asarray(a), None, X.device, X.comm)
        self.mean_ = as_dnd(saved["mean"])
        self.components_ = as_dnd(saved["components"])
        self.singular_values_ = as_dnd(saved["singular_values"])
        self.explained_variance_ = as_dnd(saved["explained_variance"])
        self.explained_variance_ratio_ = as_dnd(saved["explained_variance_ratio"])
        self._tevr = saved["tevr"]
        self.n_components_ = saved["n_components"]

    def _fitted_payload(self) -> dict:
        # device references: the writer thread does the host transfer
        as_np = lambda d: d._dense()
        return {
            "stage": "fitted",
            "mean": as_np(self.mean_),
            "components": as_np(self.components_),
            "singular_values": as_np(self.singular_values_),
            "explained_variance": as_np(self.explained_variance_),
            "explained_variance_ratio": as_np(self.explained_variance_ratio_),
            "tevr": float(self._tevr),
            "n_components": int(self.n_components_),
        }

    def fit(self, X: DNDarray, y=None) -> "PCA":
        """Estimate principal components (pca.py:210).

        Two stages: the moments (the column means and variances from ONE
        read of ``X``, :func:`~heat_tpu.core.statistics.mean_var`), then the
        solver.  The randomized solver is ONE program that makes neither a
        centred copy of ``X`` nor ``U`` (:func:`_randomized_program`), so a
        fit reads ``X`` ``2 iterated_power + 3`` times and holds beside it
        only the ``rows x (n_components + n_oversamples)`` sketches.

        With ``checkpoint_every``/``checkpoint_dir`` set, the two stages
        each commit a checkpoint; ``resume_from=dir`` skips every completed
        stage — a fit killed between the stages resumes with only the
        solver left, and a fully fitted checkpoint restores without
        touching the data.  The recomputed stages are deterministic
        functions of X and the restored state, so a resumed fit reproduces
        the uninterrupted result exactly."""
        if not isinstance(X, DNDarray):
            raise TypeError(f"X must be a DNDarray, got {type(X)}")
        if X.ndim != 2:
            raise ValueError(f"X must be 2D, got {X.ndim}D")
        if y is not None:
            raise ValueError("PCA is an unsupervised transform; y must be None")
        n, f = X.shape
        with _span("ht.decomposition.PCA.fit", rows=n, features=f, split=X.split, solver=self.svd_solver,
                   components=self.n_components) as root:
            self._fit(X, root)
        return self

    def _fit(self, X: DNDarray, root) -> None:
        from ..core import random as ht_random
        from ..core import statistics
        from ..resilience.faults import inject

        writer = self._checkpointer(for_write=True)
        restored = None
        if self.resume_from is not None:
            reader = self._checkpointer(for_write=False)
            step = reader.latest_step() if reader is not None else None
            if step is not None:
                saved = reader.restore(step)
                if saved.get("stage") == "fitted":
                    self._restore_fitted(saved, X)
                    return
                restored = saved

        n, f = X.shape
        k, rtol = self._rank(min(n, f))
        if self.random_state is not None:
            ht_random.seed(self.random_state)
        if self.svd_solver == "randomized":
            if k is None:
                raise ValueError("randomized solver requires an integer n_components")
            # the sketch's key is drawn before the moments: after them the fit is ONE program
            key = ht_random._next_key()
            power_iter = 0 if self.iterated_power == "auto" else int(self.iterated_power)
            # reads of X: the moments' one (unless restored) and the solver's 2 per turn
            root.attrs.update(passes=(restored is None or "var" not in restored) + 2 * power_iter + 2)

        # async stage writes are drained on every exit path, so a
        # caller (or a test) listing the checkpoint directory right
        # after fit() raises/returns sees a deterministic step set
        solver_span = None
        try:
            wrap = lambda a: DNDarray.from_dense(a, None, X.device, X.comm)
            if restored is None:
                inject("pca.stage", stage="moments")
                with _span("pca.stage", stage="moments"):
                    mean, var = statistics.mean_var(X, axis=0, ddof=1)
                if writer is not None:
                    # device references, not host copies: the snapshot is free and
                    # the device-to-host transfer runs on the writer thread
                    writer.save(_STAGE_MEAN, {"stage": "mean", "mean": mean._dense(), "var": var._dense()})
            elif "var" in restored:
                mean, var = (wrap(jnp.asarray(restored[key])) for key in ("mean", "var"))
            else:
                # a checkpoint of an older fit holds the mean alone: the variances take one read
                mean = wrap(jnp.asarray(restored["mean"]))
                var = statistics.var(X, axis=0, ddof=1)
            self.mean_ = mean
            inject("pca.stage", stage="solver")
            # stage heartbeat; closed in the finally so an aborted solve
            # still records its span (and never leaks nesting depth)
            solver_span = _span("pca.stage", stage="solver", solver=self.svd_solver)
            solver_span.__enter__()

            if self.svd_solver == "full":
                centered = X - mean
                U, S, V = _exact_svd(centered)
                s = S._dense()
                kk = k if k is not None else min(n, f)
                self.components_ = DNDarray.from_dense(V._dense()[:, :kk].T, None, X.device, X.comm)
                self.singular_values_ = DNDarray.from_dense(s[:kk], None, X.device, X.comm)
                ev = s**2 / max(n - 1, 1)
                self.explained_variance_ = DNDarray.from_dense(ev[:kk], None, X.device, X.comm)
                ratio = ev / jnp.maximum(jnp.sum(ev), 1e-30)
                self.explained_variance_ratio_ = DNDarray.from_dense(ratio[:kk], None, X.device, X.comm)
                self._tevr = jnp.sum(ratio[:kk])
                self.n_components_ = kk
            elif self.svd_solver == "hierarchical":
                centered = X - mean
                if rtol is not None:
                    U, S, V, err = svdtools.hsvd_rtol(centered, rtol=rtol, compute_sv=True)
                else:
                    U, S, V, err = svdtools.hsvd_rank(centered, maxrank=k, compute_sv=True)
                self.components_ = DNDarray.from_dense(V._dense().T, None, X.device, X.comm)
                self.singular_values_ = S
                s = S._dense()
                ev = s**2 / max(n - 1, 1)
                self.explained_variance_ = DNDarray.from_dense(ev, None, X.device, X.comm)
                ratio = ev / jnp.maximum(jnp.sum(var._dense()), 1e-30)
                self.explained_variance_ratio_ = DNDarray.from_dense(ratio, None, X.device, X.comm)
                self._tevr = 1.0 - err**2
                self.n_components_ = int(s.shape[0])
            else:  # randomized
                # over a mesh the rows as they lie, the padding counted out inside the program
                x = X.larray_padded if X.split == 0 else X._dense()
                dispatch.record_external_dispatch()
                vt, s, ev, ratio, tevr = _randomized_fit(
                    x, mean._dense(), var._dense(), key, n=n, k=k,
                    ell=min(k + self.n_oversamples, n, f), power_iter=power_iter)
                self.components_ = wrap(vt)
                self.singular_values_ = wrap(s)
                self.explained_variance_ = wrap(ev)
                self.explained_variance_ratio_ = wrap(ratio)
                self._tevr = tevr
                self.n_components_ = k
            if writer is not None:
                writer.save(_STAGE_FITTED, self._fitted_payload())
        finally:
            if solver_span is not None:
                solver_span.__exit__(*sys.exc_info())
            if writer is not None:
                if sys.exc_info()[0] is None:
                    writer.close()
                else:
                    try:  # the body exception wins over a writer error
                        writer.close()
                    except BaseException:  # lint: allow H501(body exception wins over a writer error)
                        pass

    def _rank(self, rank_cap: int):
        """``(k, rtol)``: the number of components asked for (None for a
        float ``n_components``), or the hierarchical solver's tolerance."""
        if isinstance(self.n_components, float):
            if not 0.0 < self.n_components <= 1.0:
                raise ValueError("float n_components must be in (0, 1]")
            return None, (1 - self.n_components) ** 0.5
        return (min(self.n_components, rank_cap) if self.n_components else rank_cap), None

    def transform(self, X: DNDarray) -> DNDarray:
        """Project onto the principal axes (pca.py:380).

        Runs under the PCA precision scope: with a tolerance-policy bf16
        request active (``HEAT_TPU_PREDICT_DTYPE=bfloat16``), the
        projection matmul takes bf16 operands with f32 accumulation
        pinned — rounding enters only through the one-time quantization
        of the centered data and the fitted axes, keeping the projected
        coordinates within the declared rtol of the native path."""
        if self.components_ is None:
            raise RuntimeError("fit needs to be called before transform")
        if not isinstance(X, DNDarray):
            raise TypeError(f"X must be a DNDarray, got {type(X)}")
        from ..analysis import precision_policy as _pp
        from ..core.linalg import basics

        with _pp.scope("PCA"):
            centered = X - self.mean_
            if _pp.active_compute_dtype() == "bfloat16":
                xd = centered._dense().astype(jnp.bfloat16)
                w = self.components_._dense().T.astype(jnp.bfloat16)
                proj = jnp.matmul(xd, w, preferred_element_type=jnp.float32)
                split = 0 if X.split == 0 else None
                return DNDarray.from_dense(proj, split, X.device, X.comm)
            return basics.matmul(centered, self.components_.T)

    def inverse_transform(self, X: DNDarray) -> DNDarray:
        """Back-project to the original space (pca.py:430)."""
        if self.components_ is None:
            raise RuntimeError("fit needs to be called before inverse_transform")
        from ..core.linalg import basics

        return basics.matmul(X, self.components_) + self.mean_
