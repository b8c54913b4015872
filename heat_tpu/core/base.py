"""Estimator base classes, analog of heat/core/base.py (base.py:13-321),
plus the shared resumable-fit machinery (checkpoint_every / resume_from)
the iterative estimators build on."""

from __future__ import annotations

import inspect
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

__all__ = [
    "BaseEstimator",
    "ClassificationMixin",
    "ClusteringMixin",
    "RegressionMixin",
    "TransformMixin",
    "is_classifier",
    "is_estimator",
    "is_clusterer",
    "is_regressor",
    "is_transformer",
    "lazy_scalar_property",
    "resumable_fit_loop",
    "validate_resume_params",
]


def validate_resume_params(
    checkpoint_every: Optional[int],
    checkpoint_dir: Optional[str],
    resume_from: Optional[str],
) -> None:
    """Shared constructor validation for the resumable-fit parameters."""
    if checkpoint_every is not None:
        if not isinstance(checkpoint_every, int) or checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every must be a positive int, got {checkpoint_every!r}"
            )
        if checkpoint_dir is None and resume_from is None:
            raise ValueError(
                "checkpoint_every requires checkpoint_dir (or resume_from) "
                "to name the checkpoint directory"
            )


def resumable_fit_loop(
    run_chunk: Callable,
    init_state: Callable,
    max_iter: int,
    tol: float,
    checkpoint_every: Optional[int] = None,
    checkpoint_dir: Optional[str] = None,
    resume_from: Optional[str] = None,
    site: str = "estimator.iter",
    what: str = "iterate",
    converged_when: Optional[Callable[[float, float], bool]] = None,
    exhausted_converges: bool = True,
) -> Tuple[object, int]:
    """Drive an on-device fit loop in resumable, divergence-guarded chunks.

    The fast paths of the iterative estimators run their whole fit as ONE
    on-device ``lax.while_loop`` (zero host syncs).  With
    ``checkpoint_every=N`` the same loop body runs in chunks of N
    iterations — each chunk is still one device program — and between
    chunks the iterate is (a) checked finite (:class:`DivergenceError`
    carrying the last good iterate on NaN/Inf), (b) offered to the fault
    injector (site ``<estimator>.iter`` — the hook kill-and-resume tests
    script), and (c) checkpointed through the filesystem-native
    :class:`~heat_tpu.utils.checkpoint.Checkpointer`.  The iteration
    sequence is identical to the uninterrupted loop, so a killed fit
    resumed from its last checkpoint reproduces the uninterrupted result
    exactly.

    ``run_chunk(state, n)`` runs at most ``n`` iterations from ``state``
    and returns ``(new_state, iters_run, shift)`` (device values);
    ``init_state()`` builds the initial iterate (only called when not
    resuming, so RNG draws consumed by initialization are not replayed
    on resume).  ``converged_when(shift, tol)`` must mirror the device
    loop's own stop test (default ``shift <= tol``) so a chunk boundary
    never stops the fit one iteration early or late relative to the
    uninterrupted loop.  Returns ``(final_state, total_iterations)``.

    Checkpoint writes are **asynchronous** by default (overlap layer,
    docs/overlap.md): chunk *k*'s atomic write runs on a background
    writer while chunk *k+1* computes on device, and the loop drains it
    (``wait()``) before evaluating the next chunk boundary — so the
    fault/kill semantics are unchanged (a kill at boundary *k+1* always
    finds chunk *k* durable, exactly like the synchronous loop) and the
    loop never returns before its final checkpoint is committed.
    ``HEAT_TPU_ASYNC_CKPT=0`` restores fully synchronous saves.

    ``exhausted_converges`` controls what a short chunk (``iters_run <
    n``) means.  For the finite fits it means the device loop's own stop
    test fired inside the chunk — converged (the default).  The online
    estimators (heat_tpu/streaming, chunk = stream window) set it False:
    a short chunk there means the stream head ran dry, so the loop
    checkpoints ``converged=False`` and returns — a later call with the
    same directory resumes and keeps consuming where the committed
    offset (inside ``state``) left off, instead of early-returning on a
    fit that never actually converged.
    """
    import os as _os
    import sys as _sys
    import time as _time

    from ..resilience.errors import DivergenceError, PreemptedError  # lazy: avoid import cycles
    from ..resilience.faults import inject
    from ..resilience.guard import all_finite
    from ..telemetry import metrics as _tm
    from ..telemetry.spans import span as _span
    from ..utils.checkpoint import Checkpointer
    from ..utils.overlap import async_checkpoint_enabled
    from ._env import env_str
    from .preempt import preemption_gate

    # fit heartbeat: iterations/s of the most recent chunk and its
    # convergence delta, refreshed at every chunk boundary so a stalled
    # or diverging long fit is visible from telemetry.snapshot();
    # fit.heartbeat_ts is the liveness signal /healthz judges staleness
    # against (HEAT_TPU_HEALTH_MAX_AGE_S, telemetry/server.py)
    iter_rate_g = _tm.gauge("fit.iter_rate", "iterations/s of the last fit chunk")
    shift_g = _tm.gauge("fit.shift", "convergence delta of the last fit chunk")
    heartbeat_g = _tm.gauge(
        "fit.heartbeat_ts", "unix time of the last resumable-fit chunk boundary"
    )
    # cross-process liveness: with HEAT_TPU_HEARTBEAT_FILE set, every
    # chunk boundary also touches a file, so an external supervisor (the
    # elastic process supervisor, docs/elasticity.md) can distinguish a
    # computing worker from a hung one without an HTTP scrape
    hb_file = env_str("HEAT_TPU_HEARTBEAT_FILE")

    def _beat() -> None:
        heartbeat_g.set(_time.time())
        if hb_file:
            try:
                _os.close(_os.open(hb_file, _os.O_CREAT | _os.O_WRONLY, 0o644))
                _os.utime(hb_file, None)
            except OSError:
                pass  # liveness signal is best-effort; never fail the fit

    ckpt = None
    directory = checkpoint_dir or resume_from
    if directory is not None and checkpoint_every is not None:
        ckpt = Checkpointer(directory)
        if async_checkpoint_enabled():
            ckpt = ckpt.as_async()

    state = None
    total = 0
    if resume_from is not None:
        reader = ckpt if ckpt is not None else Checkpointer(resume_from)
        step = reader.latest_step()
        if step is not None:
            saved = reader.restore(step)
            state = saved["state"]
            total = int(saved["n_iter"])
            if saved.get("converged") or total >= max_iter:
                return state, total
    if state is None:
        state = init_state()

    chunk = checkpoint_every if checkpoint_every is not None else max_iter
    # device references, not host copies: the last-good iterate only
    # converts to a host array if a DivergenceError actually needs it
    last_good = (state, total)
    try:
        while total < max_iter:
            n = min(chunk, max_iter - total)
            _beat()  # entering a chunk counts as alive
            t0 = _time.perf_counter()
            # heartbeat span: one per chunk, attrs filled in once the
            # chunk's device values are known
            with _span("fit.chunk", site=site) as sp:
                new_state, iters_dev, shift_dev = run_chunk(state, n)
                iters = int(iters_dev)
                shift = float(shift_dev)
            elapsed = _time.perf_counter() - t0
            sp.attrs.update(iters=iters, shift=shift, total=total + iters)
            _beat()
            iter_rate_g.set(iters / elapsed if elapsed > 0 else 0.0)
            shift_g.set(shift)
            total += iters
            if ckpt is not None:
                # the previous chunk's async write overlapped this
                # chunk's compute; drain it before the boundary so a
                # scripted kill/fault here sees it durable (sync: no-op)
                ckpt.wait()
            inject(site, iteration=total)
            if not all_finite(new_state):
                raise DivergenceError(
                    f"non-finite values in {what} at iteration {total} — the fit "
                    f"has diverged; last finite {what} is at iteration {last_good[1]}",
                    iteration=total,
                    # dict (pytree) states pass through structured; array
                    # states convert like before
                    last_good=(
                        last_good[0]
                        if isinstance(last_good[0], dict)
                        else np.asarray(last_good[0])
                    ),
                    last_good_iteration=last_good[1],
                )
            state = new_state
            stop_test = converged_when if converged_when is not None else (lambda s, t: s <= t)
            short_chunk = iters < n
            converged = stop_test(shift, tol) or (exhausted_converges and short_chunk)
            if ckpt is not None:
                ckpt.save(
                    total,
                    {
                        "state": state,
                        "n_iter": total,
                        "shift": shift,
                        "converged": bool(converged),
                    },
                )
            if converged or short_chunk:
                # a short chunk always ends the loop; with
                # exhausted_converges=False it ends it PAUSED (the
                # checkpoint above committed converged=False, so a
                # resume keeps going when more stream data arrives)
                break
            # QoS preemption poll — after the boundary checkpoint is
            # scheduled, so the pause is durable, and only for fits
            # that actually checkpoint (take(durable=False) refuses and
            # counts the refusal).  The qos.preempt site fires only
            # when the gate is honored, so a scripted kill here lands
            # at the exact yield moment; raising instead pauses
            # cooperatively — either way a resume_from the same
            # directory reproduces the uninterrupted result bitwise.
            preempt_reason = preemption_gate().take(durable=ckpt is not None)
            if preempt_reason is not None:
                inject("qos.preempt", iteration=total, reason=preempt_reason)
                raise PreemptedError(
                    f"{what} fit preempted at iteration {total} "
                    f"({preempt_reason}); resume from {directory!r} to "
                    "continue the identical iteration sequence",
                    iteration=total,
                    checkpoint_dir=directory,
                    reason=preempt_reason,
                )
            last_good = (state, total)
    finally:
        if ckpt is not None:
            if _sys.exc_info()[0] is None:
                ckpt.close()  # final write durable before the fit returns
            else:
                try:  # body exception wins over a late writer error
                    ckpt.close()
                except BaseException:  # lint: allow H501(body exception wins over a late writer error)
                    pass
    return state, total


def lazy_scalar_property(attr: str, kind: type = float, doc: Optional[str] = None) -> property:
    """Property converting a stored device scalar to a host ``kind`` lazily.

    Fits store 0-d device values in ``attr`` so they never block on a
    device->host sync; the host conversion happens once, on first access, and the
    converted value is cached back.  Shared by the cluster/PCA/Lasso/
    GaussianNB estimators (one pattern, one implementation)."""

    def fget(self):
        v = getattr(self, attr)
        if v is not None and not isinstance(v, kind):
            v = kind(v)
            setattr(self, attr, v)
        return v

    def fset(self, value):
        setattr(self, attr, value)

    return property(fget, fset, doc=doc or f"Lazy host {kind.__name__} of ``{attr}``.")


class BaseEstimator:
    """sklearn-compatible estimator base (base.py:13-95)."""

    @classmethod
    def _parameter_names(cls) -> List[str]:
        init = cls.__init__
        if init is object.__init__:
            return []
        sig = inspect.signature(init)
        return [
            name
            for name, p in sig.parameters.items()
            if name != "self" and p.kind not in (p.VAR_POSITIONAL, p.VAR_KEYWORD)
        ]

    def get_params(self, deep: bool = True) -> Dict:
        """Parameters of this estimator (base.py:30)."""
        params = {}
        for key in self._parameter_names():
            value = getattr(self, key, None)
            if deep and hasattr(value, "get_params"):
                for sub_key, sub_value in value.get_params().items():
                    params[f"{key}__{sub_key}"] = sub_value
            params[key] = value
        return params

    def set_params(self, **params) -> "BaseEstimator":
        """Set estimator parameters (base.py:60)."""
        if not params:
            return self
        valid = self.get_params(deep=True)
        for key, value in params.items():
            key, _, sub_key = key.partition("__")
            if key not in valid:
                raise ValueError(f"Invalid parameter {key} for estimator {self}.")
            if sub_key:
                valid[key].set_params(**{sub_key: value})
            else:
                setattr(self, key, value)
        return self

    def __repr__(self, indent: int = 1) -> str:
        params = ", ".join(f"{k}={v!r}" for k, v in self.get_params(deep=False).items())
        return f"{self.__class__.__name__}({params})"


class ClassificationMixin:
    """fit/predict protocol for classifiers (base.py:96)."""

    def fit(self, x, y):
        raise NotImplementedError()

    def fit_predict(self, x, y):
        self.fit(x, y)
        return self.predict(x)

    def predict(self, x):
        raise NotImplementedError()


class TransformMixin:
    """fit/transform protocol (base.py:143)."""

    def fit(self, x):
        raise NotImplementedError()

    def fit_transform(self, x):
        return self.fit(x).transform(x)

    def transform(self, x):
        raise NotImplementedError()


class ClusteringMixin:
    """fit/fit_predict protocol for clusterers (base.py:184)."""

    def fit(self, x):
        raise NotImplementedError()

    def fit_predict(self, x):
        self.fit(x)
        return self.predict(x)


class RegressionMixin:
    """fit/predict protocol for regressors (base.py:215)."""

    def fit(self, x, y):
        raise NotImplementedError()

    def fit_predict(self, x, y):
        self.fit(x, y)
        return self.predict(x)

    def predict(self, x):
        raise NotImplementedError()


def is_classifier(estimator) -> bool:
    """True for classifiers (base.py:260)."""
    return isinstance(estimator, ClassificationMixin)


def is_estimator(estimator) -> bool:
    """True for estimators (base.py:275)."""
    return isinstance(estimator, BaseEstimator)


def is_clusterer(estimator) -> bool:
    """True for clusterers (base.py:290)."""
    return isinstance(estimator, ClusteringMixin)


def is_regressor(estimator) -> bool:
    """True for regressors (base.py:305)."""
    return isinstance(estimator, RegressionMixin)


def is_transformer(estimator) -> bool:
    """True for transformers (base.py:320)."""
    return isinstance(estimator, TransformMixin)
