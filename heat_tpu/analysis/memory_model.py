"""Static peak-HBM estimator: predict a program's memory before XLA does.

An OOM on a TPU pod surfaces as a mid-fit crash *after* minutes of
compile; this module predicts an executable's peak device-memory
footprint from its **jaxpr alone** — shapes x dtypes, a last-use
liveness walk, donation aliasing, and per-device division from the
operand shardings — so an over-budget program is a diagnostic
(**J301**) before the first byte of HLO exists.

The model (deliberately simple, cross-checked against
``Compiled.memory_analysis()`` in tests — within 10% on the real
kernels the suite pins):

* program inputs and constants are resident for the whole program
  (caller-owned; XLA cannot reuse them) **unless donated**;
* each eqn allocates its outputs, then frees operands whose last use
  this was — peak is read *between* those two steps, like a real
  allocator holding inputs and outputs simultaneously;
* an output may **reuse** the buffer of an operand dying at the same
  eqn when it fits (XLA's in-place elementwise/fusion reuse): a chain
  ``a*b+c`` costs one intermediate, not two;
* a donated input aliases the first same-shape/dtype output
  (``input_output_alias``), making that output allocation free;
* a sharded operand costs its **per-device shard** bytes
  (``sharding.shard_shape``); intermediates inherit the division factor
  of their largest live operand (GSPMD keeps the split through
  elementwise/reduce chains — the cases the dispatch layer compiles).

``HEAT_TPU_HBM_BUDGET_BYTES`` (> 0) arms the budget check: the dispatch
compile hook emits J301 whenever a fresh executable's predicted
per-device peak exceeds it, surfaced through the ``Diagnostic`` ring,
``analysis.diags.J301``, ``/statusz`` and flight-recorder bundles like
every other finding.  The latest estimates are kept in a bounded table
(:func:`peak_summary`) read by the introspection surfaces.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..core import _env
from ..telemetry import metrics as _tm
from . import tsan as _tsan
from .diagnostics import Diagnostic

__all__ = [
    "PeakEstimate",
    "check_budget",
    "estimate_jaxpr_peak",
    "estimate_peak",
    "hbm_budget_bytes",
    "note_estimate",
    "peak_summary",
    "reset_estimates",
    "shard_shapes_of",
]


@dataclass
class PeakEstimate:
    """One program's predicted memory footprint (bytes).

    ``peak_bytes`` is the global (all-shards-summed) liveness peak;
    ``per_device_bytes`` divides each buffer by its modeled shard count
    — the number a single chip's HBM must hold and the one J301 checks.
    ``argument_bytes``/``output_bytes``/``temp_bytes`` decompose the
    per-device peak the way ``Compiled.memory_analysis()`` reports its
    own (arguments + outputs + temporaries), for cross-checking."""

    peak_bytes: int = 0
    per_device_bytes: int = 0
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    aliased_bytes: int = 0
    n_eqns: int = 0
    details: Dict[str, Any] = field(default_factory=dict)


def hbm_budget_bytes() -> int:
    """The armed per-device HBM budget (0 = check off)."""
    return _env.env_int("HEAT_TPU_HBM_BUDGET_BYTES")


def _nbytes(var) -> int:
    aval = getattr(var, "aval", None)
    shape = getattr(aval, "shape", None)
    dt = getattr(aval, "dtype", None)
    if shape is None or dt is None:
        return 0
    n = 1
    for s in shape:
        try:
            n *= int(s)
        except TypeError:  # pragma: no cover - symbolic dims
            return 0
    try:
        return n * np.dtype(dt).itemsize
    except TypeError:  # pragma: no cover
        return 0


def _shard_factor(var, shard_shape) -> float:
    """global bytes / per-device bytes for one invar (>= 1.0)."""
    if shard_shape is None:
        return 1.0
    aval = getattr(var, "aval", None)
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 1.0
    g = 1
    for s in shape:
        g *= int(s)
    l = 1
    for s in shard_shape:
        l *= int(s)
    if l <= 0 or g <= 0:
        return 1.0
    return max(1.0, g / l)


class _Lit:
    """Wrapper giving literal operands identity-keyed liveness slots."""

    __slots__ = ("aval",)

    def __init__(self, aval):
        self.aval = aval


def _unwrap(jaxpr):
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    while (
        len(jaxpr.eqns) == 1
        and jaxpr.eqns[0].primitive.name == "pjit"
        and jaxpr.eqns[0].params.get("jaxpr") is not None
    ):
        jaxpr = getattr(jaxpr.eqns[0].params["jaxpr"], "jaxpr",
                        jaxpr.eqns[0].params["jaxpr"])
    return jaxpr


def estimate_jaxpr_peak(
    jaxpr,
    donate_argnums: Sequence[int] = (),
    shard_shapes: Optional[Sequence] = None,
    label: str = "program",
) -> PeakEstimate:
    """Liveness-walk one (Closed)Jaxpr and return its
    :class:`PeakEstimate`.

    ``shard_shapes`` is an optional per-invar list of per-device shard
    shapes (``sharding.shard_shape(global_shape)``; None entries =
    replicated) — the per-device division of the mesh the program will
    run under."""
    jaxpr = _unwrap(jaxpr)
    invars = list(jaxpr.invars)
    constvars = list(jaxpr.constvars)
    n_in = len(invars)
    if shard_shapes is None:
        shard_shapes = [None] * n_in
    shard_shapes = list(shard_shapes) + [None] * (n_in - len(shard_shapes))

    factors: Dict[int, float] = {}
    for v, ss in zip(invars, shard_shapes):
        factors[id(v)] = _shard_factor(v, ss)
    for v in constvars:
        factors[id(v)] = 1.0

    # last textual use per var id; program outputs (and their aliases)
    # are pinned past the last eqn
    last_use: Dict[int, int] = {}
    eqns = list(jaxpr.eqns)
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not hasattr(v, "aval") or type(v).__name__ == "Literal":
                continue
            last_use[id(v)] = i
    pinned = {id(v) for v in invars} | {id(v) for v in constvars}
    out_ids = {id(v) for v in jaxpr.outvars if hasattr(v, "aval")}

    # donation: greedy-match each donated invar to the first unclaimed
    # program output of identical shape+dtype (XLA's input_output_alias)
    donated = set()
    alias_out: Dict[int, int] = {}  # outvar id -> aliased invar id
    claimed = set()
    aliased_bytes = 0
    for argnum in donate_argnums:
        if not (0 <= int(argnum) < n_in):
            continue
        iv = invars[int(argnum)]
        key = (getattr(iv.aval, "shape", None), getattr(iv.aval, "dtype", None))
        for ov in jaxpr.outvars:
            if id(ov) in claimed or not hasattr(ov, "aval"):
                continue
            if (getattr(ov.aval, "shape", None),
                    getattr(ov.aval, "dtype", None)) == key:
                claimed.add(id(ov))
                alias_out[id(ov)] = id(iv)
                donated.add(id(iv))
                aliased_bytes += _nbytes(iv)
                break

    arg_bytes_g = sum(_nbytes(v) for v in invars + constvars)
    arg_bytes_d = sum(
        _nbytes(v) / factors[id(v)] for v in invars + constvars
    )
    out_bytes_d = 0.0
    live: Dict[int, Tuple[float, float]] = {}  # id -> (global, per-device)
    for v in invars + constvars:
        b = _nbytes(v)
        live[id(v)] = (b, b / factors[id(v)])

    cur_g = float(arg_bytes_g)
    cur_d = float(arg_bytes_d)
    peak_g, peak_d = cur_g, cur_d

    for i, eqn in enumerate(eqns):
        in_ids = [
            id(v) for v in eqn.invars
            if hasattr(v, "aval") and type(v).__name__ != "Literal"
        ]
        # intermediates inherit the division of their largest live operand
        op_factor = 1.0
        best = -1.0
        for vid in in_ids:
            g, d = live.get(vid, (0.0, 0.0))
            if g > best:
                best = g
                op_factor = (g / d) if d > 0 else 1.0

        dying = [
            vid for vid in set(in_ids)
            if last_use.get(vid) == i
            and vid not in out_ids
            and (vid not in pinned or vid in donated)
        ]
        reusable = sorted(
            (live.get(vid, (0.0, 0.0))[0] for vid in dying), reverse=True
        )

        alloc_g = alloc_d = 0.0
        for ov in eqn.outvars:
            b = float(_nbytes(ov))
            if id(ov) in alias_out:
                # aliased output lives in the donated input's buffer
                src = alias_out[id(ov)]
                live[id(ov)] = live.get(src, (b, b / op_factor))
                continue
            if reusable and reusable[0] >= b > 0:
                # in-place reuse of a dying operand's buffer
                reusable[0] -= b
                reusable.sort(reverse=True)
                live[id(ov)] = (b, b / op_factor)
                continue
            alloc_g += b
            alloc_d += b / op_factor
            live[id(ov)] = (b, b / op_factor)

        cur_g += alloc_g
        cur_d += alloc_d
        peak_g = max(peak_g, cur_g)
        peak_d = max(peak_d, cur_d)

        for vid in dying:
            g, d = live.pop(vid, (0.0, 0.0))
            cur_g -= g
            cur_d -= d

    for ov in jaxpr.outvars:
        if hasattr(ov, "aval") and id(ov) not in alias_out:
            b = float(_nbytes(ov))
            out_bytes_d += live.get(id(ov), (b, b))[1]

    temp_d = max(0.0, peak_d - arg_bytes_d - out_bytes_d)
    return PeakEstimate(
        peak_bytes=int(peak_g),
        per_device_bytes=int(peak_d),
        argument_bytes=int(arg_bytes_d),
        output_bytes=int(out_bytes_d),
        temp_bytes=int(temp_d),
        aliased_bytes=int(aliased_bytes),
        n_eqns=len(eqns),
        details={"label": label},
    )


def shard_shapes_of(leaves: Sequence) -> List[Optional[Tuple[int, ...]]]:
    """Per-device shard shapes of concrete argument leaves (None =
    replicated / shardingless) — the per-invar division list
    :func:`estimate_jaxpr_peak` consumes."""
    out: List[Optional[Tuple[int, ...]]] = []
    for leaf in leaves:
        ss = None
        sharding = getattr(leaf, "sharding", None)
        shape = getattr(leaf, "shape", None)
        if sharding is not None and shape is not None:
            try:
                ss = tuple(sharding.shard_shape(tuple(shape)))
            except Exception:  # lint: allow H501(sharding probe is best-effort; replicated assumed)
                ss = None
        out.append(ss)
    return out


def estimate_peak(
    fn,
    *args,
    donate_argnums: Sequence[int] = (),
    label: Optional[str] = None,
    **kwargs,
) -> PeakEstimate:
    """Trace ``fn(*args, **kwargs)`` and estimate its peak footprint.

    Per-device division comes from the arguments' live shardings
    (``.sharding.shard_shape``) where present.  Tracing only — the
    program is never compiled or executed."""
    if label is None:
        label = getattr(fn, "__name__", None) or type(fn).__name__
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    return estimate_jaxpr_peak(
        jaxpr, donate_argnums=donate_argnums,
        shard_shapes=shard_shapes_of(jax.tree_util.tree_leaves(args)),
        label=label,
    )


def check_budget(est: PeakEstimate, label: str = "program") -> Optional[Diagnostic]:
    """The J301 verdict for one estimate against
    ``HEAT_TPU_HBM_BUDGET_BYTES`` (None when under budget / unarmed)."""
    budget = hbm_budget_bytes()
    if budget <= 0 or est.per_device_bytes <= budget:
        return None
    return Diagnostic(
        rule="J301",
        message=(
            f"predicted per-device peak {est.per_device_bytes:,} B exceeds "
            f"the HBM budget {budget:,} B "
            f"(args {est.argument_bytes:,} + out {est.output_bytes:,} + "
            f"temps {est.temp_bytes:,}) — an OOM caught before compile; "
            "shard the dominant operand, donate the dead buffer, or chunk "
            "the computation"
        ),
        location=label,
        details={
            "predicted_peak_bytes": est.per_device_bytes,
            "budget_bytes": budget,
            "argument_bytes": est.argument_bytes,
            "output_bytes": est.output_bytes,
            "temp_bytes": est.temp_bytes,
        },
    )


# ----------------------------------------------------------------------
# introspection: the latest estimates, bounded, for /statusz + bundles
# ----------------------------------------------------------------------
_ESTIMATES: "Dict[str, dict]" = {}
_EST_LOCK = _tsan.register_lock("analysis.memory_model.estimates")
_EST_MAX = 256

_PEAK_G = _tm.gauge(
    "analysis.hbm_predicted_peak_bytes",
    "latest statically predicted per-device peak HBM of a compiled program",
)
_EST_C = _tm.counter(
    "analysis.hbm_estimates", "programs walked by the static peak-HBM estimator"
)


def note_estimate(label: str, est: PeakEstimate) -> None:
    """Record one estimate into the bounded introspection table and the
    telemetry gauges (the dispatch-hook path calls this per miss)."""
    _EST_C.inc()
    _PEAK_G.set(float(est.per_device_bytes))
    with _EST_LOCK:
        _tsan.note_access("analysis.memory_model.estimates")
        if len(_ESTIMATES) >= _EST_MAX:
            _ESTIMATES.clear()
        _ESTIMATES[str(label)[:200]] = {
            "per_device_bytes": est.per_device_bytes,
            "peak_bytes": est.peak_bytes,
            "argument_bytes": est.argument_bytes,
            "output_bytes": est.output_bytes,
            "temp_bytes": est.temp_bytes,
            "n_eqns": est.n_eqns,
        }


def peak_summary() -> Dict[str, Any]:
    """The bounded per-program estimate table plus the armed budget —
    the ``analysis`` section /statusz and crash bundles embed."""
    with _EST_LOCK:
        _tsan.note_access("analysis.memory_model.estimates", write=False)
        per = dict(_ESTIMATES)
    return {
        "budget_bytes": hbm_budget_bytes(),
        "estimates": per,
    }


def reset_estimates() -> None:
    """Drop the recorded estimates (tests)."""
    with _EST_LOCK:
        _tsan.note_access("analysis.memory_model.estimates")
        _ESTIMATES.clear()
