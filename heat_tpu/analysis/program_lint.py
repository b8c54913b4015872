"""Jaxpr/HLO-level SPMD program analyzer.

heat's correctness model leaves two things implicit that only XLA sees:
the collectives GSPMD inserts behind sharded ops, and the recompiles
the jit cache performs when a cache key drifts.  This module walks the
jaxpr and the *compiled* (post-SPMD-partitioning) HLO of a program and
turns both into structured :class:`~.diagnostics.Diagnostic` records:

* **J101 — unaccounted implicit collective.**  The compiled module
  contains a collective kind (all-reduce / all-gather / all-to-all /
  collective-permute / reduce-scatter) that neither an explicit
  ``Communication`` collective nor a ``comm.account_implicit`` call
  accounted during the trace — cross-checked against the telemetry
  registry's ``comm.calls.{op}`` counters, so the comm-volume model
  (docs/observability.md) silently under-reports.
* **J102 — accidental full gather of the split axis.**  An all-gather
  whose result extent along the gather dimension is ``mesh size x`` the
  operand extent: the whole split dimension re-materializes on every
  participant (the classic resplit(None)-by-accident hazard).
* **J103 — weak-type / python-scalar recompile hazard.**  Standalone:
  an input aval carries ``weak_type=True`` (every distinct Python
  scalar *type* at that position compiles a fresh executable).  On the
  dispatch path: two executable-cache keys identical except for the
  dtype of a 0-d (scalar) leaf — the cache is being split by scalar
  dtype drift.
* **J104 — donation miss.**  An operand in ``donate_argnums`` that XLA
  did not alias to an output (the ``input_output_alias`` map of the
  compiled module): the caller gave up its buffer and got no HBM reuse
  back.
* **J105 — silent dtype promotion.**  A program input converted to a
  wider dtype of the same kind (f32 -> f64, i32 -> i64) on entry —
  usually an accidental mixed-precision operand doubling the program's
  memory traffic.

The precision/memory layer (ISSUE 12) rides the same entry points: the
jaxpr dtype-flow walker (:mod:`~heat_tpu.analysis.dtype_flow`, J201-J204)
and the static peak-HBM estimator
(:mod:`~heat_tpu.analysis.memory_model`, J301) run over every program
:func:`analyze` or the dispatch hook walks, with the active precision
policy from :mod:`~heat_tpu.analysis.precision_policy`.

Entry points: :func:`analyze` (standalone — trace, lower, compile and
check any callable) and :func:`on_dispatch_compile` /
:func:`note_dispatch_key` (the ``core/dispatch.py`` compile-path hook,
active when ``HEAT_TPU_ANALYZE`` != 0).
"""

from __future__ import annotations

import re
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..telemetry import metrics as _tm
from . import tsan as _tsan
from .diagnostics import Diagnostic, analysis_mode, emit

__all__ = [
    "analyze",
    "analyze_compiled_text",
    "analyze_jaxpr",
    "note_dispatch_key",
    "on_dispatch_compile",
    "reset_dispatch_state",
]

# HLO instruction name (left) -> comm-layer op names whose trace-time
# accounting (explicit collectives or account_implicit) covers it.  The
# *-start variants are the async forms TPU emits.
_HLO_COLLECTIVES: Dict[str, Tuple[str, ...]] = {
    "all-reduce": ("psum", "pmax", "pmin", "pscan", "exscan"),
    "all-gather": ("all_gather",),
    "all-to-all": ("all_to_all",),
    "collective-permute": ("ppermute", "ring_shift", "pscan", "exscan"),
    "reduce-scatter": ("psum_scatter",),
}

#: matches an HLO instruction *definition* of a collective, capturing the
#: result shape, the op kind and the first operand: its shape where the
#: printer writes it inline, else its name (resolved by
#: :func:`_operand_shape`), e.g.
#: ``%all-gather = f32[32,4]{1,0} all-gather(f32[4,4]{1,0} %param), ...`` or
#: ``%all-gather = f32[32,4]{1,0} all-gather(%fusion), ...``
_COLLECTIVE_DEF = re.compile(
    r"=\s+(?:\([^)]*\)|(?P<rtype>\w+)\[(?P<rshape>[0-9,]*)\])\S*\s+"
    r"(?P<op>all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter)"
    r"(?:-start)?\("
    r"\s*(?:\w+\[(?P<oshape>[0-9,]*)\]\S*\s*)?(?P<oname>%[\w.\-]+)?"
)

_DIMENSIONS = re.compile(r"dimensions=\{(\d+)\}")

#: aliased parameter numbers in the compiled module header, e.g.
#: ``input_output_alias={ {}: (0, {}, may-alias), {1}: (2, {}, must-alias) }``
#: — the ``(param, {index}, kind)`` tuples are unique to alias maps, so
#: they are matched over the whole module text (the header braces nest)
_ALIAS_PARAM = re.compile(r"\(\s*(\d+)\s*,\s*\{[^}]*\}\s*,\s*(?:may|must)-alias\s*\)")


def _parse_shape(s: Optional[str]) -> Tuple[int, ...]:
    if not s:
        return ()
    return tuple(int(x) for x in s.split(",") if x)


def _operand_shape(text: str, m: "re.Match") -> Tuple[int, ...]:
    """First-operand shape of the collective matched by ``m``: the
    inline shape, or — the HLO printer names operands without their
    shape — the shape in the operand's own definition, the nearest one
    above the use (names repeat across computations)."""
    if m.group("oshape") is not None:
        return _parse_shape(m.group("oshape"))
    if not m.group("oname"):
        return ()
    defs = re.findall(
        re.escape(m.group("oname")) + r"\s+=\s+\w+\[([0-9,]*)\]", text[: m.start()]
    )
    return _parse_shape(defs[-1]) if defs else ()


def _comm_calls_snapshot() -> Dict[str, float]:
    """Current ``comm.calls.{op}`` counter values from the telemetry
    registry — the accounting ledger explicit collectives and
    ``account_implicit`` both write at trace time."""
    out: Dict[str, float] = {}
    for name in _tm.REGISTRY.names():
        if name.startswith("comm.calls."):
            out[name[len("comm.calls."):]] = _tm.REGISTRY.get(name).value
    return out


def _accounted_delta(before: Dict[str, float]) -> Dict[str, float]:
    after = _comm_calls_snapshot()
    return {
        op: after[op] - before.get(op, 0) for op in after
        if after[op] - before.get(op, 0) > 0
    }


# ----------------------------------------------------------------------
# compiled-HLO checks (J101, J102, J104)
# ----------------------------------------------------------------------
def analyze_compiled_text(
    text: str,
    accounted: Optional[Dict[str, float]] = None,
    n_participants: Optional[int] = None,
    label: str = "program",
    donate_argnums: Sequence[int] = (),
) -> List[Diagnostic]:
    """Scan one compiled module's HLO text for collective and donation
    hazards; returns the diagnostics without emitting them.

    ``accounted`` maps comm-layer op names (``psum``, ``all_gather``,
    ...) to the number of calls accounted while the program was traced;
    a collective *kind* with zero accounted coverage is J101.
    ``n_participants`` (default: the process device count) calibrates
    the J102 full-gather test.  ``donate_argnums`` enables the J104
    aliasing check against the module's ``input_output_alias`` header.
    """
    accounted = accounted or {}
    if n_participants is None:
        n_participants = jax.device_count()
    diags: List[Diagnostic] = []

    found: Dict[str, int] = {}
    full_gathers: List[Tuple[Tuple[int, ...], Tuple[int, ...]]] = []
    for m in _COLLECTIVE_DEF.finditer(text):
        op = m.group("op")
        found[op] = found.get(op, 0) + 1
        if op == "all-gather" and n_participants > 1:
            rshape = _parse_shape(m.group("rshape"))
            oshape = _operand_shape(text, m)
            dim_m = _DIMENSIONS.search(text, m.end(), m.end() + 400)
            dim = int(dim_m.group(1)) if dim_m else 0
            if (
                len(rshape) == len(oshape)
                and dim < len(rshape)
                and oshape[dim] > 0
                and rshape[dim] == oshape[dim] * n_participants
            ):
                full_gathers.append((oshape, rshape))

    for op, n in sorted(found.items()):
        covering = _HLO_COLLECTIVES.get(op, ())
        if not any(accounted.get(c, 0) > 0 for c in covering):
            diags.append(Diagnostic(
                rule="J101",
                message=(
                    f"compiled program contains {n} GSPMD {op} collective(s) "
                    "not covered by comm accounting — wrap the launch in "
                    "comm.account_implicit(...) (or issue the collective "
                    "through the Communication wrappers) so the telemetry "
                    "comm-volume model stays truthful"
                ),
                location=label,
                details={"collective": op, "count": n,
                         "accounted": dict(accounted)},
            ))
    for oshape, rshape in full_gathers:
        diags.append(Diagnostic(
            rule="J102",
            message=(
                f"all-gather rebuilds the full split extent on every "
                f"participant ({list(oshape)} -> {list(rshape)} across "
                f"{n_participants} devices) — an accidental resplit(None); "
                "check the operand split axes of the consuming op"
            ),
            location=label,
            details={"operand_shape": list(oshape), "result_shape": list(rshape),
                     "participants": n_participants},
        ))

    if donate_argnums:
        aliased: set = set()
        if "input_output_alias" in text:
            aliased = {int(p) for p in _ALIAS_PARAM.findall(text)}
        missed = sorted(set(int(i) for i in donate_argnums) - aliased)
        if missed:
            diags.append(Diagnostic(
                rule="J104",
                message=(
                    f"donated operand(s) {missed} were not aliased to any "
                    "output (input_output_alias) — the buffer was given up "
                    "but XLA could not reuse its allocation (shape/dtype "
                    "mismatch with every output?)"
                ),
                location=label,
                details={"donate_argnums": sorted(int(i) for i in donate_argnums),
                         "aliased": sorted(aliased)},
            ))
    return diags


# ----------------------------------------------------------------------
# jaxpr checks (J103 weak types, J105 silent promotion)
# ----------------------------------------------------------------------
def analyze_jaxpr(jaxpr, label: str = "program") -> List[Diagnostic]:
    """Walk a ``ClosedJaxpr`` (or raw jaxpr) for weak-type recompile
    hazards and silent same-kind dtype widening of the inputs."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    diags: List[Diagnostic] = []
    invars = list(jaxpr.invars)
    weak = [
        i for i, v in enumerate(invars)
        if getattr(getattr(v, "aval", None), "weak_type", False)
    ]
    if weak:
        diags.append(Diagnostic(
            rule="J103",
            message=(
                f"input(s) {weak} carry weak types (Python scalars traced "
                "into the program) — every distinct scalar *type* at these "
                "positions compiles a fresh executable; pass a committed "
                "jnp/np array (or make the scalar static) to pin the "
                "cache key"
            ),
            location=label,
            details={"weak_invars": weak},
        ))

    invar_set = {id(v) for v in invars}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "convert_element_type":
            continue
        src = eqn.invars[0]
        if id(src) not in invar_set:
            continue
        aval = getattr(src, "aval", None)
        if aval is None or getattr(aval, "weak_type", False):
            continue  # weak promotions are J103's domain
        old = np.dtype(aval.dtype)
        new = np.dtype(eqn.params.get("new_dtype", old))
        if old.kind == new.kind and new.itemsize > old.itemsize:
            diags.append(Diagnostic(
                rule="J105",
                message=(
                    f"program input of dtype {old.name} is silently widened "
                    f"to {new.name} on entry — a mixed-precision operand is "
                    "promoting the whole expression; cast explicitly or fix "
                    "the wide operand"
                ),
                location=label,
                details={"from": old.name, "to": new.name,
                         "invar": invars.index(src)},
            ))
    return diags


# ----------------------------------------------------------------------
# standalone entry point
# ----------------------------------------------------------------------
def analyze(
    fn,
    *args,
    donate_argnums: Sequence[int] = (),
    static_argnums: Sequence[int] = (),
    label: Optional[str] = None,
    emit_diags: bool = False,
    policy=None,
    allowed_narrowing: Sequence[str] = (),
    **kwargs,
) -> List[Diagnostic]:
    """Trace, lower and compile ``fn(*args, **kwargs)`` and return every
    SPMD diagnostic (J101-J105), precision diagnostic (J201-J204) and
    memory-budget diagnostic (J301) found in the program.

    ``fn`` may be a plain callable or an existing ``jax.jit`` object;
    the analysis never *executes* the program (tracing and XLA
    compilation only), so donated buffers are not consumed.  Explicit
    collectives and ``comm.account_implicit`` calls made while ``fn``
    traces are credited against the J101 cross-check — analyzing the
    production launch wrapper therefore checks the real accounting, not
    a test double.  ``emit_diags=True`` additionally routes each finding
    through :func:`~.diagnostics.emit` (telemetry counters + ring +
    warn/raise per the current mode).  ``policy`` is a precision-policy
    document for the J201/J204 checks (default: the active predict
    scope's); ``allowed_narrowing`` lists extra dtype names explicit
    narrowing casts may target without J201."""
    if label is None:
        label = getattr(fn, "__name__", None) or type(fn).__name__
    jitted = fn
    if not hasattr(jitted, "lower"):
        jit_kwargs: Dict[str, Any] = {}
        if donate_argnums:
            jit_kwargs["donate_argnums"] = tuple(donate_argnums)
        if static_argnums:
            jit_kwargs["static_argnums"] = tuple(static_argnums)
        jitted = jax.jit(fn, **jit_kwargs)

    before = _comm_calls_snapshot()
    lowered = jitted.lower(*args, **kwargs)
    accounted = _accounted_delta(before)
    compiled = lowered.compile()

    diags: List[Diagnostic] = []
    # jaxpr-level checks need the *traceable* function: the original fn,
    # or a jit object's wrapped target
    traceable = fn if not hasattr(fn, "lower") else getattr(fn, "__wrapped__", None)
    jaxpr = None
    if traceable is not None:
        try:
            jaxpr = jax.make_jaxpr(
                traceable, static_argnums=tuple(static_argnums)
            )(*args, **kwargs)
        except Exception:  # lint: allow H501(jaxpr derivation is best-effort)
            jaxpr = None
    if jaxpr is not None:
        diags.extend(analyze_jaxpr(jaxpr, label=label))
        # precision layer: dtype-flow (J201-J204) + peak-HBM (J301) over
        # the same derived jaxpr, with the caller's (or the active
        # predict scope's) precision policy
        from . import dtype_flow as _dflow
        from . import memory_model as _mmodel

        diags.extend(_dflow.analyze_dtype_flow(
            jaxpr, label=label, policy=policy,
            allowed_narrowing=allowed_narrowing,
        ))
        try:
            est = _mmodel.estimate_jaxpr_peak(
                jaxpr, donate_argnums=donate_argnums,
                shard_shapes=_mmodel.shard_shapes_of(
                    jax.tree_util.tree_leaves(args)
                ),
                label=label,
            )
        except Exception:  # lint: allow H501(estimator is best-effort; the J1xx checks still run)
            est = None
        if est is not None:
            budget_diag = _mmodel.check_budget(est, label)
            if budget_diag is not None:
                diags.append(budget_diag)
    else:
        in_avals = jax.tree_util.tree_leaves(getattr(lowered, "in_avals", ()))
        weak = [i for i, a in enumerate(in_avals)
                if getattr(a, "weak_type", False)]
        if weak:
            diags.append(Diagnostic(
                rule="J103",
                message=(
                    f"input(s) {weak} carry weak types — every distinct "
                    "Python scalar type at these positions compiles a "
                    "fresh executable"
                ),
                location=label,
                details={"weak_invars": weak},
            ))

    try:
        texts = compiled.as_text()
    except Exception:  # lint: allow H501(HLO text retrieval is best-effort)
        texts = ""
    if isinstance(texts, (list, tuple)):  # pragma: no cover - multi-module
        texts = "\n".join(texts)
    diags.extend(analyze_compiled_text(
        texts,
        accounted=accounted,
        label=label,
        donate_argnums=donate_argnums,
    ))
    if emit_diags:
        for d in diags:
            emit(d)
    return diags


# ----------------------------------------------------------------------
# dispatch compile-path hook
# ----------------------------------------------------------------------
#: normalized-key -> set of full keys seen; detects executable-cache
#: entries that differ only in a scalar leaf's dtype (J103 at the
#: dispatch level).  Bounded: cleared past _KEY_TRACK_MAX groups.
_KEY_GROUPS: Dict[Any, set] = {}
_KEY_LOCK = _tsan.register_lock("analysis.program_lint.keys")
_KEY_TRACK_MAX = 4096

_ANALYZED = _tm.counter(
    "analysis.programs_analyzed", "dispatch compiles walked by the program lint"
)


def reset_dispatch_state() -> None:
    """Drop the dispatch-key tracking state (tests)."""
    with _KEY_LOCK:
        _tsan.note_access("analysis.program_lint.key_groups")
        _KEY_GROUPS.clear()


def _normalize_leaf_spec(spec):
    """A leaf spec with scalar (0-d) dtypes erased, so keys that differ
    only in scalar dtype collapse into one group."""
    if (
        isinstance(spec, tuple)
        and len(spec) == 3
        and isinstance(spec[0], tuple)
        and spec[0] == ()
    ):
        return ((), "<scalar>", spec[2])
    return spec


def note_dispatch_key(key) -> None:
    """Record one executable-cache miss key; emits J103 when a previous
    key in the same normalized group differs only in a scalar leaf's
    dtype (the weak-type / python-scalar recompile hazard, observed as
    real cache-entry churn)."""
    if analysis_mode() == "off" or not isinstance(key, tuple):
        return
    norm = tuple(
        tuple(_normalize_leaf_spec(s) for s in part)
        if isinstance(part, tuple) else part
        for part in key
    )
    if norm == key:
        return  # no scalar leaves -> nothing to group
    with _KEY_LOCK:
        _tsan.note_access("analysis.program_lint.key_groups")
        if len(_KEY_GROUPS) > _KEY_TRACK_MAX:
            _KEY_GROUPS.clear()
        group = _KEY_GROUPS.setdefault(norm, set())
        fresh_pair = key not in group and len(group) >= 1
        group.add(key)
        group_size = len(group)
    if fresh_pair:
        emit(Diagnostic(
            rule="J103",
            message=(
                "executable-cache keys differ only in a python-scalar "
                "leaf's dtype — the same program is recompiling per scalar "
                "type (weak-type drift); pin the scalar's dtype at the "
                "call site"
            ),
            location=str(key[0]),
            source="dispatch",
            details={"group_size": group_size},
        ))


def on_dispatch_compile(entry, leaves, key, donate_argnums: Sequence[int] = ()) -> None:
    """Compile-path hook: called by ``core/dispatch.py`` on every
    executable-cache miss when ``HEAT_TPU_ANALYZE`` != 0.

    Re-lowers the fresh jit entry at the miss arguments and walks the
    compiled module for J101/J102/J104 (the accounting cross-check uses
    the comm counters bumped while the entry traced — explicit
    collectives fire at trace time, which happens inside this call),
    then derives the jaxpr for the precision layer: dtype-flow J201-J204
    against the active predict scope's policy, and the static peak-HBM
    estimate (recorded into :func:`~.memory_model.peak_summary` and
    checked against ``HEAT_TPU_HBM_BUDGET_BYTES`` — J301).  Costs
    roughly one extra trace+compile per cache miss; off mode never
    reaches this function."""
    if analysis_mode() == "off":
        return
    try:
        before = _comm_calls_snapshot()
        lowered = entry.lower(*leaves)
        accounted = _accounted_delta(before)
        text = lowered.compile().as_text()
        if isinstance(text, (list, tuple)):  # pragma: no cover
            text = "\n".join(text)
    except Exception:  # lint: allow H501(analysis must never break the dispatch path)
        return  # analysis must never break the dispatch path
    _ANALYZED.inc()
    label = str(key[0]) if isinstance(key, tuple) and key else "dispatch"
    for d in analyze_compiled_text(
        text, accounted=accounted, label=label, donate_argnums=donate_argnums
    ):
        emit(d)

    from . import dtype_flow as _dflow
    from . import memory_model as _mmodel
    from . import precision_policy as _pp

    try:
        jaxpr = jax.make_jaxpr(entry)(*leaves)
    except Exception:  # lint: allow H501(jaxpr derivation is best-effort; the HLO checks above ran)
        return
    for d in _dflow.analyze_dtype_flow(
        jaxpr, label=label, policy=_pp.active_policy()
    ):
        emit(d)
    try:
        est = _mmodel.estimate_jaxpr_peak(
            jaxpr, donate_argnums=donate_argnums,
            shard_shapes=_mmodel.shard_shapes_of(leaves), label=label,
        )
    except Exception:  # lint: allow H501(estimator is best-effort; dtype flow already emitted)
        return
    _mmodel.note_estimate(label, est)
    budget_diag = _mmodel.check_budget(est, label)
    if budget_diag is not None:
        emit(budget_diag)
