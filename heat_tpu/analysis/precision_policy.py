"""Per-estimator precision policies: the contract mixed precision serves under.

ROADMAP item 2 asks for bf16/int8 inference paths "gated by a
bitwise-vs-tolerance policy per estimator".  This module is that gate's
source of truth: every served estimator kind declares ONCE, in the
:data:`POLICIES` table below, whether its predictions are

* ``bitwise`` — byte-identical to the reference fit/predict path; the
  compute dtype set is exactly the native one and any low-precision
  compute is a policy violation (**J204**); or
* ``tolerance`` — allowed to run lower-precision compute (the listed
  ``compute_dtypes``) as long as predictions stay within ``rtol`` of the
  native path — the contract the bf16 KMeans/cdist predict core serves
  under, and what tests/benches assert.

Like ``KNOBS`` / ``KNOWN_SITES`` / ``LOCK_REGISTRY``, the table is a
**pure literal** (``ast.literal_eval``-parseable, no imports needed to
read it).  It is enforced at three choke points:

1. **the dispatch analyze hook** — predict paths enter
   :func:`scope`, and the jaxpr dtype-flow walker
   (:mod:`~heat_tpu.analysis.dtype_flow`) checks every compiled
   program's float compute dtypes against the active scope's policy
   (J204), and sanctions narrowing casts into a tolerance policy's
   allowed dtypes (J201);
2. **the model store** — :func:`~heat_tpu.serving.model_io.save_model`
   records the declared policy and the export's effective compute dtype
   in the version metadata, and
   :meth:`~heat_tpu.serving.registry.ModelRegistry.load` REFUSES to
   activate a version whose recorded compute dtype (or the serving
   process's current one) violates the recorded policy
   (:class:`PrecisionPolicyError`);
3. **the batch CLI** — ``python -m heat_tpu.analysis --rules J2,J3``
   traces every served estimator's predict program and runs the full
   J2xx/J301 check set over it.

``HEAT_TPU_PREDICT_DTYPE`` selects the low-precision compute dtype for
*tolerance*-policy estimators (empty = native float32 everywhere); a
dtype a kind's policy does not allow is ignored for that kind with a
J204 diagnostic, never silently served.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
from typing import Any, Dict, Iterable, List, Optional

from ..core import _env
from .diagnostics import Diagnostic, ProgramLintError, emit

__all__ = [
    "POLICIES",
    "PrecisionPolicyError",
    "active_compute_dtype",
    "active_policy",
    "check_load",
    "compute_dtype",
    "policy_for",
    "refresh_env",
    "scope",
    "set_predict_dtype",
    "validate_policy",
]

#: Every served estimator kind's precision contract: kind -> {mode,
#: compute_dtypes[, rtol]}.  ``mode`` is "bitwise" (predictions must be
#: byte-identical to the native path; compute_dtypes is exactly the
#: native dtype) or "tolerance" (low-precision compute from
#: ``compute_dtypes`` is allowed; predictions must stay within ``rtol``
#: of the native path).  ``compute_dtypes`` lists the allowed float
#: compute dtypes, native first.  PURE LITERAL — readable with
#: ast.literal_eval, like KNOBS / KNOWN_SITES / LOCK_REGISTRY.
POLICIES = {
    # KMeans predict is an argmin over euclidean distances: tolerant to
    # bf16 rounding of the cross term (norms and accumulation stay f32 —
    # see spatial/distance.py), so it serves under a tolerance contract.
    # The FIT's precision is not a policy but part of the program, the same
    # on every backend and under every scope (cluster/kmeans.py::_half_d2):
    # points rounded to bfloat16 in both Lloyd products, centers float32 in
    # |c|^2 and in the cross term alike, float32 sums.  No mode here and no
    # scope there switches it.
    "KMeans": {"mode": "tolerance", "rtol": 0.02, "compute_dtypes": ("float32", "bfloat16")},
    # median/medoid geometry ties break on exact comparisons; low
    # precision can flip a tie permanently -> bitwise only
    "KMedians": {"mode": "bitwise", "compute_dtypes": ("float32",)},
    "KMedoids": {"mode": "bitwise", "compute_dtypes": ("float32",)},
    # PCA transform is one projection matmul: bf16 operands with f32
    # accumulation keep the coordinates within rtol of the native path
    # (tests/test_precision.py measures the bound on fitted components)
    "PCA": {"mode": "tolerance", "rtol": 0.02, "compute_dtypes": ("float32", "bfloat16")},
    "Lasso": {"mode": "bitwise", "compute_dtypes": ("float32",)},
    # KNN serves under a tolerance contract on the DISTANCE stage only
    # (same bf16 cross-term core as KMeans); the predicted labels stay
    # bitwise — votes are argmax over discrete counts, and the tests
    # assert exact label agreement on margin-separated data, so a bf16
    # rounding that flips the k-th neighbor set is a test failure, not
    # an accepted tolerance
    "KNeighborsClassifier": {"mode": "tolerance", "rtol": 0.02, "compute_dtypes": ("float32", "bfloat16")},
}

_MODES = ("bitwise", "tolerance")

#: dtype names a policy may list / the predict knob may select
_KNOWN_DTYPES = ("float32", "bfloat16", "float16", "float64")


class PrecisionPolicyError(ProgramLintError):
    """A precision-policy violation surfaced at an enforcement point
    (registry load refusal, a J204 verdict in raise mode).  Carries the
    J204 :class:`~.diagnostics.Diagnostic` like every program-lint
    error."""


def policy_for(kind: str) -> Optional[Dict[str, Any]]:
    """The declared policy of estimator ``kind`` (None if undeclared)."""
    return POLICIES.get(kind)


def validate_policy(policy: Dict[str, Any]) -> Dict[str, Any]:
    """Shape-check a policy document (the ``save_model(policy=...)``
    override); returns it normalized (compute_dtypes as a tuple)."""
    if not isinstance(policy, dict):
        raise TypeError(f"policy must be a dict, got {type(policy).__name__}")
    mode = policy.get("mode")
    if mode not in _MODES:
        raise ValueError(f"policy mode must be one of {_MODES}, got {mode!r}")
    dtypes = tuple(policy.get("compute_dtypes") or ())
    if not dtypes:
        raise ValueError("policy must list at least one compute dtype")
    unknown = [d for d in dtypes if d not in _KNOWN_DTYPES]
    if unknown:
        raise ValueError(
            f"unknown compute dtype(s) {unknown}; expected from {_KNOWN_DTYPES}"
        )
    out = dict(policy)
    out["compute_dtypes"] = dtypes
    if mode == "tolerance":
        rtol = float(policy.get("rtol", 0.0))
        if rtol <= 0.0:
            raise ValueError("a tolerance policy needs rtol > 0")
        out["rtol"] = rtol
    return out


# ----------------------------------------------------------------------
# the predict compute dtype (HEAT_TPU_PREDICT_DTYPE)
# ----------------------------------------------------------------------
def _parse_predict_dtype(raw: Optional[str]) -> str:
    if raw is None:
        raw = _env.knob_default("HEAT_TPU_PREDICT_DTYPE")
    raw = str(raw).strip().lower()
    if raw in ("", "0", "off", "float32", "f32", "native"):
        return ""
    aliases = {"bf16": "bfloat16", "f16": "float16"}
    raw = aliases.get(raw, raw)
    if raw not in _KNOWN_DTYPES:
        raise ValueError(
            f"HEAT_TPU_PREDICT_DTYPE={raw!r}: expected one of "
            f"{('',) + _KNOWN_DTYPES}"
        )
    return raw


_PREDICT_DTYPE = _parse_predict_dtype(os.environ.get("HEAT_TPU_PREDICT_DTYPE"))

#: kinds whose disallowed knob override already emitted a J204 (warn once)
_WARNED_KINDS: set = set()


def set_predict_dtype(name: str) -> str:
    """Set the low-precision predict compute dtype at runtime (overrides
    the env knob; ``""`` restores native f32); returns the previous
    setting.  Bench/test hook."""
    global _PREDICT_DTYPE
    prev = _PREDICT_DTYPE
    _PREDICT_DTYPE = _parse_predict_dtype(name)
    _WARNED_KINDS.clear()
    return prev


def refresh_env() -> str:
    """Re-read ``HEAT_TPU_PREDICT_DTYPE`` (tests that flip the env var
    mid-process); returns the new setting."""
    global _PREDICT_DTYPE
    _PREDICT_DTYPE = _parse_predict_dtype(os.environ.get("HEAT_TPU_PREDICT_DTYPE"))
    _WARNED_KINDS.clear()
    return _PREDICT_DTYPE


def compute_dtype(kind: str) -> str:
    """The effective predict compute dtype name for estimator ``kind``.

    The requested low-precision dtype (``HEAT_TPU_PREDICT_DTYPE`` /
    :func:`set_predict_dtype`) applies only when ``kind``'s declared
    policy is ``tolerance`` AND lists it; any other combination serves
    native (``compute_dtypes[0]``, f32 for undeclared kinds) — a
    disallowed request additionally emits one J204 diagnostic per kind,
    so a mis-set knob is visible, never silently obeyed."""
    pol = POLICIES.get(kind)
    native = pol["compute_dtypes"][0] if pol else "float32"
    req = _PREDICT_DTYPE
    if not req or req == native:
        return native
    if pol is not None and pol["mode"] == "tolerance" and req in pol["compute_dtypes"]:
        return req
    if kind not in _WARNED_KINDS:
        _WARNED_KINDS.add(kind)
        emit(Diagnostic(
            rule="J204",
            message=(
                f"HEAT_TPU_PREDICT_DTYPE={req} is not allowed by the "
                f"{kind} precision policy "
                f"({'undeclared' if pol is None else pol['mode']}) — "
                f"serving native {native} instead; widen the POLICIES "
                "entry (with a tolerance bench) to opt this kind in"
            ),
            location=kind,
            details={"requested": req, "policy": dict(pol) if pol else None},
        ))
    return native


# ----------------------------------------------------------------------
# the active predict scope (the dispatch-hook enforcement point)
# ----------------------------------------------------------------------
#: (kind, policy dict, effective compute dtype name) of the innermost
#: active predict scope; contextvars survive the same-thread dispatch
#: compile the scope's ops trigger
_SCOPE: contextvars.ContextVar = contextvars.ContextVar(
    "heat_tpu_precision_scope", default=None
)


@contextlib.contextmanager
def scope(kind: str):
    """Declare that ops issued inside the block implement ``kind``'s
    predict path: the dispatch analyze hook checks every program
    compiled in here against ``kind``'s policy (J204), sanctions
    tolerance-mode narrowing (J201), and the cdist low-precision path
    reads the effective compute dtype from here."""
    pol = POLICIES.get(kind)
    token = _SCOPE.set((kind, pol, compute_dtype(kind)))
    try:
        yield
    finally:
        _SCOPE.reset(token)


def active_policy() -> Optional[Dict[str, Any]]:
    """The innermost active scope's policy document (None outside any
    predict scope or for an undeclared kind)."""
    s = _SCOPE.get()
    return s[1] if s is not None else None


def active_compute_dtype() -> Optional[str]:
    """The active scope's effective LOW-PRECISION compute dtype name, or
    None when unscoped / serving native — the one cheap query the cdist
    hot path makes per call."""
    s = _SCOPE.get()
    if s is None:
        return None
    dt = s[2]
    return dt if dt not in ("", "float32", "float64") else None


# ----------------------------------------------------------------------
# the registry enforcement point
# ----------------------------------------------------------------------
def _allowed(policy: Dict[str, Any], dtype_name: str) -> bool:
    dtypes = tuple(policy.get("compute_dtypes") or ())
    if policy.get("mode") == "bitwise":
        # bitwise = exactly the native dtype; a second listed dtype
        # would make "bitwise" unfalsifiable
        return bool(dtypes) and dtype_name == dtypes[0]
    return dtype_name in dtypes


def check_load(
    kind: str,
    policy: Optional[Dict[str, Any]],
    recorded_dtype: Optional[str],
    label: str = "registry.load",
) -> None:
    """Registry-load choke point: raise :class:`PrecisionPolicyError`
    when the version's recorded compute dtype, or the serving process's
    current effective one, violates the version's recorded policy.

    ``policy``/``recorded_dtype`` come from the version metadata
    ``save_model`` wrote; versions saved before the policy layer (both
    None) load unchecked.  The refusal is unconditional — unlike the
    analyzers it does NOT honor ``HEAT_TPU_ANALYZE=off``: activating a
    version that cannot meet its own declared contract is never a
    warning."""
    if policy is None:
        return
    violations: List[str] = []
    if recorded_dtype and not _allowed(policy, str(recorded_dtype)):
        violations.append(
            f"exported with compute dtype {recorded_dtype} but declares "
            f"{policy.get('mode')} over {tuple(policy.get('compute_dtypes') or ())}"
        )
    # the dtype the predict path will ACTUALLY use in this process
    # (knob gated by the global POLICIES table), checked against the
    # VERSION'S recorded policy: a version declaring bitwise must not
    # activate into a process whose knob serves it low-precision
    serving_dtype = compute_dtype(kind)
    if not _allowed(policy, serving_dtype):
        violations.append(
            f"serving process computes {kind} predictions in "
            f"{serving_dtype} (HEAT_TPU_PREDICT_DTYPE) but the version "
            f"declares {policy.get('mode')} over "
            f"{tuple(policy.get('compute_dtypes') or ())}"
        )
    if not violations:
        return
    diag = Diagnostic(
        rule="J204",
        message=(
            f"refusing to activate {kind} model version: "
            + "; ".join(violations)
        ),
        location=label,
        source="dispatch",
        details={"kind": kind, "policy": dict(policy),
                 "recorded_dtype": recorded_dtype},
    )
    emit(diag, mode="off")  # count + ring; the refusal below is the verdict
    raise PrecisionPolicyError(diag)


def policies_for_kinds(kinds: Iterable[str]) -> Dict[str, Dict[str, Any]]:
    """Declared policies for the given kinds (the CLI batch report)."""
    return {k: dict(POLICIES[k]) for k in kinds if k in POLICIES}
