"""Transparent cached-executable dispatch for the generic op wrappers.

Every NumPy-level op funnels through the four wrappers in
``core/_operations.py``.  Before this module they executed as *eager*
``jnp`` calls: one Python dispatch + one XLA executable launch per op, so
a chain like ``(a * b + c).sum()`` paid four launches — the measured
bottleneck of the bench history (dpsgd only beats the dispatch floor by
hand-batching steps, kmeans idles against the host-sync floor).  This
module gives the hot paths the two levers ``fusion.jit`` offers opt-in,
without any user opt-in:

1. **Executable cache** — op applications route through ``jax.jit``-
   compiled closures keyed by ``(op, abstract spec of operands, static
   kwargs)``.  Repeated shapes (the only case in iterative ML: kmeans /
   lasso / PCA / DASO loops) hit a compiled executable instead of
   re-dispatching through the jnp eager machinery.  Hit/miss/dispatch
   counters are exposed via :func:`cache_stats`.

2. **Lazy elementwise chain fusion** — element-wise results carry a small
   pending-expression node (:class:`PendingExpr`: bounded depth,
   element-wise only, same padded layout) instead of a concrete buffer.
   Materialization is deferred until a reduction, collective, indexing,
   print, or host read forces it — every such boundary funnels through
   ``DNDarray.larray_padded`` — at which point the whole chain compiles
   as ONE fused XLA computation through the cache.  A reduction/cum-op
   consuming a pending chain folds the chain, the pad-masking, and the
   reduction into a single cached executable (:func:`chain_apply`).

3. **Buffer donation** — in-place ops (``resplit_``, ``out=`` stores,
   ``__iadd__``-style dunders) donate the target's dead backing buffer to
   the compiled program (``donate_argnums``), letting XLA reuse the HBM
   allocation instead of holding both generations live.  Donation is
   gated on a CPython refcount proof that the buffer is unshared
   (:func:`_refcount_at_most`): two DNDarrays sharing a backing array, a
   pending expression holding the buffer as a leaf, or a user-held
   ``larray_padded`` reference all suppress donation (donating a shared
   buffer would poison every other holder).  An in-place store whose
   chain reads the target's buffer and no other of its size does not run
   where it is asked for: the target takes the chain
   (:func:`defer_store`) and its first reader runs all that has gathered
   as one donating store.

Environment knobs (all default-on):

* ``HEAT_TPU_DISPATCH_CACHE=0`` — disable the executable cache (ops run
  as plain eager jnp calls; fusion is disabled too).
* ``HEAT_TPU_FUSION=0`` — disable lazy chain fusion only.
* ``HEAT_TPU_FUSION_DEPTH`` — max pending-chain depth before a subchain
  is materialized (default 16).
* ``HEAT_TPU_DONATE=0`` — disable buffer donation.
* ``HEAT_TPU_ANALYZE=1`` (or ``raise``) — run the SPMD program analyzer
  (``heat_tpu/analysis/program_lint.py``) over every freshly compiled
  executable: unaccounted implicit collectives, accidental full
  gathers, scalar-dtype recompile churn and donation misses surface as
  structured diagnostics (default ``0`` = off, free).  The same hook
  arms the precision layer (``analysis/dtype_flow.py`` — J201-J204
  against the active predict scope's precision policy) and the static
  peak-HBM estimator (``analysis/memory_model.py`` — J301 against
  ``HEAT_TPU_HBM_BUDGET_BYTES``).
* ``HEAT_TPU_COST_ANALYSIS=1`` — record XLA's per-executable cost/memory
  analysis on every cache miss (``dispatch.flops_total``,
  :func:`cost_summary`; surfaced by the introspection server's
  ``/statusz`` page and the crash flight recorder).  Default off.

See ``docs/dispatch.md`` for the cache-key, donation, and
fusion-boundary semantics, and ``docs/static_analysis.md`` for the
analyzer.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
import warnings
from collections import OrderedDict
from typing import Any, Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import tsan as _tsan
from ..resilience.errors import ChecksumError as _ChecksumError
from ..resilience.errors import PermanentFault as _PermanentFault
from ..resilience.faults import inject as _inject
from ..telemetry import metrics as _tm
from ..telemetry.spans import span as _span
from . import _env as _env
from . import aot_cache as _aot

__all__ = [
    "PendingExpr",
    "batch_bucket",
    "cache_enabled",
    "cache_keys",
    "cache_stats",
    "chain_apply",
    "clear_cache",
    "cost_accounting_enabled",
    "cost_summary",
    "defer_store",
    "eager_apply",
    "fusion_enabled",
    "make_node",
    "materialize",
    "meter_costs",
    "record_external_dispatch",
    "reset_stats",
    "set_cost_accounting",
]


# knob reads go through the central registry (core/_env.py KNOBS) —
# the H201 lint rule enforces the same table on direct os.environ reads
_CACHE_ENABLED = _env.env_flag("HEAT_TPU_DISPATCH_CACHE")
_FUSION_ENABLED = _env.env_flag("HEAT_TPU_FUSION")
_DONATE_ENABLED = _env.env_flag("HEAT_TPU_DONATE")
FUSION_DEPTH = _env.env_int("HEAT_TPU_FUSION_DEPTH")
_CACHE_MAXSIZE = _env.env_int("HEAT_TPU_DISPATCH_CACHE_SIZE")
_COST_ENABLED = _env.env_flag("HEAT_TPU_COST_ANALYSIS")


def cache_enabled() -> bool:
    """Whether the executable cache is active."""
    return _CACHE_ENABLED


def fusion_enabled() -> bool:
    """Whether lazy elementwise chain fusion is active."""
    return _CACHE_ENABLED and _FUSION_ENABLED


# ----------------------------------------------------------------------
# counters + cache.  The counters live in the shared telemetry registry
# (``telemetry.snapshot()`` reports them as ``dispatch.*`` alongside the
# resilience/overlap/comm domains); :func:`cache_stats` is a thin
# byte-compatible view over them.
# ----------------------------------------------------------------------
_COUNTER_NAMES = ("hits", "misses", "dispatches", "fused_ops", "donations",
                  "external_dispatches", "compile_fallbacks", "stores",
                  "deferred_stores")
_C = {n: _tm.counter(f"dispatch.{n}") for n in _COUNTER_NAMES}

#: per-compile wall time (jit trace + XLA compile + first execution of a
#: fresh cache entry), milliseconds
_COMPILE_MS = _tm.histogram(
    "dispatch.compile_ms", "wall time of compile+first-run per cache miss"
)

#: LRU of compiled executables.  Bounded because op callables created
#: inline (lambdas/partials) key by object identity and would otherwise
#: accumulate one dead entry per call.
_cache: "OrderedDict[Any, Callable]" = OrderedDict()

#: the cache (and the cost records below) are mutated per dispatch on
#: the fit thread but ITERATED from other threads — /statusz handler
#: threads call cache_keys()/cost_summary(), the crash excepthook reads
#: the same, and iterating an OrderedDict mid-insert raises.  Every
#: mutation and every iteration holds this registered lock; lookups
#: inside the lock keep the LRU move-to-end ordered.
_CACHE_LOCK = _tsan.register_lock("dispatch.cache")

_tm.gauge("dispatch.cache_size", "live compiled-executable cache entries",
          fn=lambda: len(_cache))
_tm.gauge(
    "dispatch.hit_rate", "hits / (hits + misses), 0.0 before any lookup",
    fn=lambda: (
        _C["hits"].value / t if (t := _C["hits"].value + _C["misses"].value) else 0.0
    ),
)

#: (op, arg avals, kwargs) -> ShapeDtypeStruct; jax.eval_shape costs
#: ~1 ms per call, far too slow to pay per dispatch.
_aval_cache: dict = {}


def cache_stats() -> dict:
    """Snapshot of the dispatch counters.

    ``hits``/``misses`` count executable-cache lookups, ``dispatches``
    the compiled-program launches issued through this layer,
    ``fused_ops`` the number of elementwise/reduce ops folded into those
    launches (fused_ops >> dispatches means fusion is working), and
    ``donations`` the in-place launches that donated a dead buffer,
    ``stores`` the in-place stores asked of :func:`cast_store` (``a += b``,
    ``out=``, a scaler's ``copy=False``): ``stores - donations`` of a
    region that only stores is the number of them that wrote a second
    buffer.  ``deferred_stores`` counts the in-place stores that did not
    run where they were asked for (:func:`defer_store`): each is folded
    into the one store that its target's first reader runs, and steps
    ``stores`` there, once for all of them.
    ``external_dispatches`` are launches recorded by consumers with their
    own jitted programs (kmeans' Lloyd loop, lasso's CD loop,
    ``fusion.jit``).  ``compile_fallbacks`` counts compiled executions
    that failed (trace/compile error, injected compile fault) and were
    re-run eagerly instead of crashing the op.  ``hit_rate`` is
    hits / (hits + misses), 0.0 before any lookup.

    A thin view over the shared telemetry registry (the counters live
    there as ``dispatch.*``); ``telemetry.snapshot()`` reports the same
    values alongside every other domain."""
    s = {n: _C[n].value for n in _COUNTER_NAMES}
    total = s["hits"] + s["misses"]
    s["hit_rate"] = (s["hits"] / total) if total else 0.0
    s["cache_size"] = len(_cache)
    return s


def reset_stats() -> None:
    """Zero all dispatch counters (the compiled cache itself is kept);
    delegates to ``telemetry.reset_all("dispatch")``."""
    from ..telemetry import reset_all

    reset_all("dispatch")


def clear_cache() -> None:
    """Drop every compiled executable (and its cost records) and zero
    the counters."""
    with _CACHE_LOCK:
        _tsan.note_access("dispatch.cache")
        _cache.clear()
        _cost_records.clear()
    _aval_cache.clear()
    reset_stats()


def record_external_dispatch(n: int = 1) -> None:
    """Count ``n`` executable launches made outside this layer (consumers
    with their own jitted programs: kmeans/lasso loops, ``fusion.jit``)."""
    _C["external_dispatches"].inc(n)


def batch_bucket(n: int, cap: Optional[int] = None) -> int:
    """Quantized leading extent for variable-size batch dispatch.

    Online traffic produces arbitrary batch sizes; dispatching each one
    verbatim would mint one executable-cache key (and one XLA compile)
    per distinct size.  Padding every batch up to the next power of two
    — capped at ``cap``, which is itself a valid bucket — bounds the key
    set to ``log2(cap)+1`` shapes: after one warmup pass per bucket, any
    traffic mix runs entirely on cache hits.  The serving layer's
    request coalescer (``heat_tpu/serving/coalescer.py``) pads with real
    rows to the returned extent, so the bucket is the true shape every
    cached program sees."""
    n = int(n)
    if n < 1:
        raise ValueError(f"batch size must be >= 1, got {n}")
    b = 1 << (n - 1).bit_length()
    if cap is not None:
        cap = int(cap)
        if n > cap:
            raise ValueError(f"batch size {n} exceeds the bucket cap {cap}")
        b = min(b, cap)
    return b


# ----------------------------------------------------------------------
# per-executable cost accounting (docs/observability.md).  Opt-in
# (``HEAT_TPU_COST_ANALYSIS=1``): on every cache miss the fresh entry is
# re-lowered and XLA's own cost/memory analysis recorded per cache key —
# the static FLOP and byte footprint of every compiled program in the
# process, the inventory ``/statusz`` and the flight recorder expose.
# Off by default because the extra trace+lower per miss is measurable in
# compile-bound workloads (the analysis itself is version-guarded: any
# jax without Lowered.cost_analysis just records nothing).
# ----------------------------------------------------------------------
_FLOPS_TOTAL = _tm.counter(
    "dispatch.flops_total", "XLA cost-analysis flops summed over compiled executables"
)
_COST_BYTES_TOTAL = _tm.counter(
    "dispatch.cost_bytes_total",
    "XLA cost-analysis bytes-accessed summed over compiled executables",
)

#: cache key -> cost record for every analyzed executable (bounded like
#: the executable cache itself)
_cost_records: "OrderedDict[Any, dict]" = OrderedDict()


def cost_accounting_enabled() -> bool:
    """Whether per-executable cost accounting is active."""
    return _COST_ENABLED


def set_cost_accounting(enabled: bool) -> bool:
    """Enable/disable cost accounting at runtime (overrides the env
    knob); returns the previous state.  Bench/test hook."""
    global _COST_ENABLED
    prev = _COST_ENABLED
    _COST_ENABLED = bool(enabled)
    return prev


def _fmt_key_part(obj, depth: int = 0) -> str:
    if callable(obj):
        return getattr(obj, "__name__", type(obj).__name__)
    if isinstance(obj, (tuple, list)):
        if depth > 3:
            return "(...)"
        return "(" + ", ".join(_fmt_key_part(o, depth + 1) for o in obj) + ")"
    return str(obj)


def _key_repr(key, limit: int = 200) -> str:
    """Compact human-readable form of a cache key (op names, shapes,
    dtypes; shardings stringify) for /statusz and crash bundles."""
    s = _fmt_key_part(key)
    return s if len(s) <= limit else s[: limit - 3] + "..."


def cache_keys() -> list:
    """Readable reprs of every live executable-cache key (insertion
    order: oldest first, like the LRU itself)."""
    with _CACHE_LOCK:
        _tsan.note_access("dispatch.cache", write=False)
        keys = list(_cache)
    return [_key_repr(k) for k in keys]


def cost_summary() -> dict:
    """Cost-accounting view: totals plus the per-executable records.

    ``{"enabled", "executables", "flops_total", "bytes_total",
    "per_key": {key_repr: {flops, bytes_accessed, ...}}}`` — totals are
    the ``dispatch.flops_total`` / ``dispatch.cost_bytes_total``
    registry counters, so they survive record eviction."""
    with _CACHE_LOCK:
        _tsan.note_access("dispatch.cache", write=False)
        per_key = {_key_repr(k): dict(v) for k, v in _cost_records.items()}
        n = len(_cost_records)
    return {
        "enabled": _COST_ENABLED,
        "executables": n,
        "flops_total": _FLOPS_TOTAL.value,
        "bytes_total": _COST_BYTES_TOTAL.value,
        "per_key": per_key,
    }


def _record_cost(key, entry, leaves) -> None:
    """Record XLA's cost/memory analysis for a freshly compiled entry.

    Version-guarded throughout: ``Lowered.cost_analysis`` /
    ``Compiled.memory_analysis`` vary across jax releases (dict vs
    [dict], missing attributes) — any probe failure records nothing and
    costs nothing downstream."""
    try:
        lowered = entry.lower(*leaves)
        cost = lowered.cost_analysis()
    except Exception:  # lint: allow H501(version-guarded probe; accounting is best-effort)
        return
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    if not isinstance(cost, dict):
        return
    rec = {
        "flops": float(cost.get("flops", 0.0) or 0.0),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0) or 0.0),
        "transcendentals": float(cost.get("transcendentals", 0.0) or 0.0),
    }
    try:
        mem = lowered.compile().memory_analysis()
        for attr in (
            "generated_code_size_in_bytes",
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
        ):
            v = getattr(mem, attr, None)
            if v is not None:
                rec[attr] = int(v)
    except Exception:  # lint: allow H501(memory analysis missing on this jax/backend; flops still recorded)
        pass
    _FLOPS_TOTAL.inc(rec["flops"])
    _COST_BYTES_TOTAL.inc(rec["bytes_accessed"])
    with _CACHE_LOCK:
        _tsan.note_access("dispatch.cache")
        _cost_records[key] = rec
        while len(_cost_records) > _CACHE_MAXSIZE:
            _cost_records.popitem(last=False)


class CostMeter:
    """Accumulated analyzed cost of the executables one thread ran.

    Filled by :func:`_run` while a :func:`meter_costs` scope is active
    on the thread: each dispatch adds its cached cost record's FLOPs and
    bytes.  ``unmetered_calls`` counts dispatches with no cost record
    (accounting off, analysis probe failed, or record evicted) — the
    honesty counter that distinguishes "this work was free" from "this
    work was invisible"."""

    __slots__ = ("flops", "bytes_accessed", "calls", "unmetered_calls")

    def __init__(self) -> None:
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.calls = 0
        self.unmetered_calls = 0


_METER_TLS = threading.local()


@contextlib.contextmanager
def meter_costs():
    """Meter the analyzed cost of every dispatch on this thread.

    Thread-local and re-entrant (a nested scope meters independently
    and the outer scope resumes on exit) — the serving path wraps one
    coalesced batch's inference in a scope to attribute the batch's
    FLOPs/bytes to its member tenants (/tenantz).  Yields the
    :class:`CostMeter` being filled."""
    meter = CostMeter()
    prev = getattr(_METER_TLS, "meter", None)
    _METER_TLS.meter = meter
    try:
        yield meter
    finally:
        _METER_TLS.meter = prev


def _meter_note(key) -> None:
    """Add ``key``'s analyzed cost to the thread's active meter (no-op
    without one: one TLS read on the unmetered hot path)."""
    meter = getattr(_METER_TLS, "meter", None)
    if meter is None:
        return
    rec = None
    if key is not None:
        with _CACHE_LOCK:
            _tsan.note_access("dispatch.cache", write=False)
            rec = _cost_records.get(key)
    if rec is None:
        meter.unmetered_calls += 1
        return
    meter.calls += 1
    meter.flops += rec["flops"]
    meter.bytes_accessed += rec["bytes_accessed"]


def _note_lookup(hit: bool) -> None:
    _C["hits" if hit else "misses"].inc()


# ----------------------------------------------------------------------
# pending expressions
# ----------------------------------------------------------------------
class PendingExpr:
    """One deferred elementwise op over pending/concrete operands.

    ``args`` holds :class:`PendingExpr` children and/or concrete
    ``jax.Array`` leaves; ``shape``/``dtype`` are the abstract result
    (from a cached ``jax.eval_shape``), so metadata queries never force
    materialization.  Nodes are immutable: leaves are captured as the
    *buffers* they were at op time, so later in-place mutation of an
    operand DNDarray cannot change an already-built chain's value."""

    __slots__ = ("op", "args", "kwargs", "shape", "dtype", "depth", "nops")

    def __init__(self, op, args, kwargs, shape, dtype, depth, nops):
        self.op = op
        self.args = args
        self.kwargs = kwargs
        self.shape = shape
        self.dtype = dtype
        self.depth = depth
        self.nops = nops


def _kw_key(kwargs: dict) -> Tuple:
    key = tuple(sorted(kwargs.items()))
    hash(key)  # TypeError for unhashable values -> caller falls back
    return key


def _leaf_spec(buf) -> Tuple:
    return (tuple(buf.shape), buf.dtype, getattr(buf, "sharding", None))


def _abstract_eval(op, arg_avals: Tuple, kw_key: Tuple, kwargs: dict):
    k = (op, arg_avals, kw_key)
    out = _aval_cache.get(k)
    if out is None:
        out = jax.eval_shape(
            lambda *a: op(*a, **kwargs),
            *[jax.ShapeDtypeStruct(s, d) for (s, d) in arg_avals],
        )
        if len(_aval_cache) > 4 * _CACHE_MAXSIZE:
            _aval_cache.clear()
        _aval_cache[k] = out
    return out


def make_node(op, args: Sequence, kwargs: Optional[dict] = None) -> Optional[PendingExpr]:
    """Build a pending elementwise node, or None when it cannot be fused
    (fusion disabled, unhashable kwargs, abstract eval failed).

    ``args`` entries are PendingExpr or concrete jax.Array.  A child at
    the depth limit is materialized on the spot so chains stay bounded."""
    if not fusion_enabled():
        return None
    kwargs = kwargs or {}
    try:
        kw_key = _kw_key(kwargs)
    except TypeError:
        return None
    args = tuple(
        materialize(a) if isinstance(a, PendingExpr) and a.depth >= FUSION_DEPTH else a
        for a in args
    )
    arg_avals = []
    depth = 1
    nops = 1
    for a in args:
        if isinstance(a, PendingExpr):
            depth = max(depth, a.depth + 1)
            nops += a.nops
            arg_avals.append((a.shape, a.dtype))
        else:
            arg_avals.append((tuple(a.shape), a.dtype))
    try:
        aval = _abstract_eval(op, tuple(arg_avals), kw_key, kwargs)
    except Exception:  # lint: allow H501(unfusable node -> eager path, no fault sites inside)
        return None
    if not isinstance(aval, jax.ShapeDtypeStruct):
        return None  # multi-output ops don't fuse
    return PendingExpr(op, args, kwargs, tuple(aval.shape), aval.dtype, depth, nops)


def _astype(a, *, dtype):
    return a.astype(dtype)


#: (type, value, dtype) -> 0-d jax.Array.  Scalar operands used to pay a
#: full factories.array round trip (0-d DNDarray + device_put) on EVERY
#: op — the profile-dominant cost of a chain like (a*b+c)/2.0.  Reusing
#: one leaf object also dedups the compiled program's inputs.
_scalar_cache: dict = {}


def scalar_leaf(value, dtype):
    """Cached 0-d constant leaf for a Python-number operand.

    Built as a NUMPY scalar, never ``jnp.asarray``: inside an active
    trace (``ht.jit`` bodies) jnp constants come back as tracers, and a
    cached tracer leaks into every later call outside the trace.  A
    numpy constant is always concrete, converts on the compiled call,
    and constant-folds when the consumer itself is being traced."""
    key = (type(value), value, dtype)
    buf = _scalar_cache.get(key)
    if buf is None:
        buf = np.asarray(value, dtype)
        if len(_scalar_cache) > 512:
            _scalar_cache.clear()
        _scalar_cache[key] = buf
    return buf


def _stored(a, one, *, dtype):
    """``a`` as a store into a ``dtype`` buffer would have left it: cast,
    and rounded THERE.  Inside one program the compiler rewrites across
    what used to be two (``(x / a) / b`` becomes ``x / (a * b)``; a product
    and the sum behind it contract into one rounding), and a deferred store
    must not change a bit of what the stores would have written one by one.
    ``one`` is a 1 the compiler cannot know (a run-time scalar): the
    product changes no value, stands between the patterns, and absorbs a
    contraction (``fma(t, 1, b)`` is ``t + b`` rounded once, as stored)."""
    return a.astype(dtype) * one


def cast_node(x, dtype) -> Optional[PendingExpr]:
    """Pending ``astype`` node (the __local_op float32 pre-cast)."""
    return make_node(_astype, (x,), {"dtype": dtype})


def _mask_pad(a, *, split, extent, neutral):
    """Overwrite the canonical padding rows with ``neutral`` (the fused
    equivalent of ``DNDarray._masked``)."""
    idx = jax.lax.broadcasted_iota(jnp.int32, a.shape, split)
    return jnp.where(idx < extent, a, jnp.asarray(neutral, a.dtype))


# ----------------------------------------------------------------------
# linearization + compiled-program cache
# ----------------------------------------------------------------------
def _linearize(root):
    """DAG -> (topo-ordered node list, deduped leaf list, leaf arg-slot
    counts).  Node refs are ``(is_node, index)`` pairs; shared subtrees
    and repeated leaves dedupe by object identity, so a buffer is passed
    to the compiled program exactly once however often it appears."""
    nodes: list = []
    node_ix: dict = {}
    leaves: list = []
    leaf_ix: dict = {}
    leaf_slots: dict = {}

    def walk(n):
        if isinstance(n, PendingExpr):
            ix = node_ix.get(id(n))
            if ix is None:
                refs = tuple(walk(a) for a in n.args)
                nodes.append((n.op, n.kwargs, refs))
                ix = len(nodes) - 1
                node_ix[id(n)] = ix
            return (True, ix)
        ix = leaf_ix.get(id(n))
        if ix is None:
            leaves.append(n)
            ix = len(leaves) - 1
            leaf_ix[id(n)] = ix
        leaf_slots[ix] = leaf_slots.get(ix, 0) + 1
        return (False, ix)

    walk(root)
    # ``walk`` names itself: unless the cell is emptied the cycle keeps
    # ``leaves``, and every buffer in it, alive until the collector runs,
    # and the next in-place store's refcount proof sees a buffer that a
    # finished reduction of a chain still seems to share
    del walk
    return nodes, leaves, leaf_slots


def _program_key(tag: str, nodes, leaves, extra: Tuple = ()) -> Tuple:
    nk = tuple((op, _kw_key(kwargs), refs) for op, kwargs, refs in nodes)
    lk = tuple(_leaf_spec(l) for l in leaves)
    key = (tag, nk, lk) + extra
    hash(key)
    return key


def _build_program(nodes):
    def program(*leaves):
        vals = []
        for op, kwargs, refs in nodes:
            args = [vals[i] if is_node else leaves[i] for (is_node, i) in refs]
            vals.append(op(*args, **kwargs))
        return vals[-1]
    return program


def _build_lazy_program(nodes):
    """The program of a ``lazy`` :func:`chain_apply`: the last node's op is
    handed, in the value's place, the chain itself as a function of what is
    taken of each leaf."""
    *chain, (op, kwargs, ((is_node, ix),)) = nodes
    evaluate = _build_program(chain) if is_node else None

    def program(*leaves):
        def value(take):
            taken = [take(leaf) for leaf in leaves]
            return evaluate(*taken) if is_node else taken[ix]
        return op(value, **kwargs)
    return program


def _eval_nodes(nodes, leaves, build=_build_program):
    """Uncached eager evaluation (cache disabled / unhashable key)."""
    return build(nodes)(*leaves)


def _maybe_analyze(entry, leaves, key, donate_argnums=()) -> None:
    """SPMD program-lint hook on the compile path (docs/static_analysis.md).

    Off mode (``HEAT_TPU_ANALYZE=0``, the default) costs one lazy-import
    dict lookup and a string compare per cache MISS — nothing per hit.
    Warn/raise mode re-lowers the fresh entry and walks its compiled
    module for unaccounted collectives, full gathers and donation misses
    (roughly one extra trace+compile per miss)."""
    from ..analysis.diagnostics import analysis_mode

    if analysis_mode() == "off":
        return
    from ..analysis.program_lint import note_dispatch_key, on_dispatch_compile

    note_dispatch_key(key)
    on_dispatch_compile(entry, leaves, key, donate_argnums=donate_argnums)


def _aot_entry(key, jitted, leaves):
    """AOT-cache resolution of a fresh in-memory miss (armed caches
    only; see ``core/aot_cache.py``).  Returns the compiled executable
    to install — a deserialized artifact when one matches, else the
    eagerly ``lower().compile()``-ed (and persisted) program — or
    ``None`` to fall back to the plain lazy-jit path.  Either way the
    compile accounting (``dispatch.compile`` span + ``compile_ms``)
    happens HERE, so callers treat the returned entry as warm."""
    compiled = _aot.load(key)
    if compiled is not None:
        return compiled
    try:
        t0 = time.perf_counter()
        with _span("dispatch.compile", aot=True):
            compiled = jitted.lower(*leaves).compile()
        _COMPILE_MS.observe((time.perf_counter() - t0) * 1e3)
    except Exception:  # lint: allow H501(AOT pre-compile failed; the lazy jit path re-raises any real error)
        return None
    _aot.save(key, compiled)
    return compiled


def _get_compiled(key, builder, donate_argnums=None, out_sharding=None, leaves=None):
    """Cached jitted executable for ``key``; returns ``(entry, fresh)``
    where ``fresh`` marks a miss — the first execution of a fresh entry
    pays trace+compile, which :func:`_run` times into the
    ``dispatch.compile_ms`` histogram.

    With the on-disk AOT cache armed (``HEAT_TPU_AOT_CACHE``) and
    ``leaves`` provided, a miss first consults the artifact store: a
    matching artifact installs a deserialized executable with NO
    compile; otherwise the program is compiled eagerly and persisted.
    Both AOT paths return ``fresh=False`` (their compile accounting is
    internal); donated entries and armed-analyzer runs
    (``HEAT_TPU_ANALYZE``) keep the plain lazy-jit path — the analyzer
    must be able to re-lower the fresh entry."""
    with _CACHE_LOCK:
        _tsan.note_access("dispatch.cache")
        entry = _cache.get(key)
        if entry is not None:
            _cache.move_to_end(key)
    if entry is not None:
        _note_lookup(True)
        return entry, False
    _note_lookup(False)
    _inject("dispatch.compile")
    jit_kwargs: dict = {}
    if out_sharding is not None:
        jit_kwargs["out_shardings"] = out_sharding
    if donate_argnums:
        jit_kwargs["donate_argnums"] = donate_argnums
    entry = jax.jit(builder(), **jit_kwargs)
    fresh = True
    if leaves is not None and not donate_argnums and _aot.enabled():
        from ..analysis.diagnostics import analysis_mode

        if analysis_mode() == "off":
            aot = _aot_entry(key, entry, leaves)
            if aot is not None:
                entry, fresh = aot, False
    with _CACHE_LOCK:
        _tsan.note_access("dispatch.cache")
        _cache[key] = entry
        while len(_cache) > _CACHE_MAXSIZE:
            _cache.popitem(last=False)
    return entry, fresh


class _launch(_span):
    """The one ``dispatch.launch`` span of a program that goes through
    :func:`_run`: opened where an entrance (``kind``: ``expr``
    :func:`materialize`, ``chain`` :func:`chain_apply`, ``apply``
    :func:`eager_apply`, ``cast_store``, ``repad``) starts to decide
    (linearize, key, cache lookup, donation proof) and ended by
    :func:`_run` at the return of the enqueue, so its interval is this
    layer's host time for the program and never the device's.  The span it
    ran under is the innermost one open on the thread; at depth 0 the
    program ran at a read outside every ``ht.*`` call.

    Attributes, plain values only (a buffer, a leaf list or a chain held
    here would be one holder more than the donation proof allows):
    ``ops`` (operations fused into the program), ``fresh`` (a cache miss;
    the ``dispatch.compile`` span lies inside), ``store`` (an in-place
    store), ``donated`` (it took the target's buffer), ``folded`` (the
    deferred stores it runs: the :func:`_stored` marks of its chain);
    ``fallback`` where the work ran eagerly after all (the compile failed,
    or the key cannot be hashed) and ``error``, the exception's type name,
    where the launch raises."""

    __slots__ = ()

    def __init__(self, kind: str, ops: int = 0, store: bool = False):
        super().__init__("dispatch.launch", kind=kind, ops=ops, fresh=False, store=store,
                         donated=False, folded=0)

    def end(self) -> None:
        """Close the interval (:func:`_run`, behind the enqueue); the
        entrance's ``with`` then has nothing left to close."""
        _span.__exit__(self, None, None, None)

    def __exit__(self, exc_type, exc, tb) -> bool:
        # a no-op after ``end``; before it the launch did not come to its
        # enqueue, and the record says why
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        return super().__exit__(exc_type, exc, tb)


def _run(compiled, leaves, n_ops: int, sp: _launch, donated: bool = False,
         fresh: bool = False, key=None):
    _C["dispatches"].inc()
    _C["fused_ops"].inc(n_ops)
    if donated:
        _C["donations"].inc()
    sp.attrs.update(fresh=fresh, donated=donated)

    def call():
        if donated:
            with warnings.catch_warnings():
                # XLA may decline an unusable donation (layout mismatch);
                # that is a perf note, not a user-facing condition
                warnings.filterwarnings("ignore", message=".*[Dd]onat")
                return compiled(*leaves)
        return compiled(*leaves)

    t0 = time.perf_counter()
    if fresh:
        # cache miss: the first call traces + compiles
        with _span("dispatch.compile", ops=n_ops):
            out = call()
    else:
        out = call()
    dt = time.perf_counter() - t0
    # the launch ends with the enqueue; what follows never waits for the
    # device: a miss's compile time and cost record, then the meter
    sp.end()
    if fresh:
        # record the wall time so ``where did the compile time go?`` is
        # answerable from telemetry
        _COMPILE_MS.observe(dt * 1e3)
        if _COST_ENABLED and key is not None:
            # outside the timed window: the accounting re-lower must not
            # inflate the compile_ms histogram it sits next to
            with warnings.catch_warnings():
                warnings.filterwarnings("ignore", message=".*[Dd]onat")
                _record_cost(key, compiled, leaves)
    _meter_note(key)
    return out


def _compiled_or_fallback(sp: _launch, key, builder, leaves, n_ops, eager_fn, out_sharding=None):
    """Run through the executable cache; on a trace/compile/run failure
    fall back to ONE eager execution instead of crashing the op, inside
    the launch's span ``sp`` and marked ``fallback`` there.

    The broken cache entry is dropped so the next call re-attempts a
    compile (a transient compile failure — injected or an XLA hiccup —
    heals itself); ``compile_fallbacks`` in :func:`cache_stats` counts
    the events and a ``RuntimeWarning`` surfaces each one.  A genuine
    error in the op (bad shapes, bad dtype) re-raises from the eager
    run, so user-facing exceptions are unchanged.  Donating paths never
    come through here: a partially-run donated program may have
    consumed its input, making re-execution unsafe."""
    try:
        compiled, fresh = _get_compiled(
            key, builder, out_sharding=out_sharding, leaves=leaves
        )
        if fresh:
            _maybe_analyze(compiled, leaves, key)
        return _run(compiled, leaves, n_ops, sp, fresh=fresh, key=key)
    except (_PermanentFault, _ChecksumError):
        # non-retryable resilience faults must propagate — an eager
        # fallback here would SWALLOW a permanent failure the caller's
        # recovery logic (and the H501 lint rule) depends on seeing
        raise
    except Exception as e:  # lint: allow H501(compile fallback; non-retryables re-raised above)
        if type(e).__name__ == "ProgramLintError":
            # raise-mode analyzer diagnostics are verdicts, not transient
            # compile failures — an eager fallback would hide exactly the
            # hazard HEAT_TPU_ANALYZE=raise exists to stop on (lazy name
            # check: importing analysis here would cycle through core)
            raise
        _C["compile_fallbacks"].inc()
        sp.attrs["fallback"] = True
        with _CACHE_LOCK:
            _tsan.note_access("dispatch.cache")
            _cache.pop(key, None)
        warnings.warn(
            f"dispatch: compiled execution failed ({type(e).__name__}: {e}); "
            "falling back to eager execution for this call",
            RuntimeWarning,
            stacklevel=3,
        )
        # the same event, routed into the alert layer: a warn-severity
        # deduplicated alert (re-fires only update value/message) so an
        # operator watching /statusz or /decisionz sees fallback storms
        # without scraping stderr for RuntimeWarnings.  Lazy import:
        # telemetry.alerts at module level would cycle through core.
        try:
            from ..telemetry import alerts as _alerts

            _alerts.fire(
                "dispatch:compile_fallback",
                severity="warn",
                message=(
                    f"compiled execution failed ({type(e).__name__}); "
                    "eager fallback taken"
                ),
                value=float(_C["compile_fallbacks"].value),
                evidence={"error": type(e).__name__,
                          "series": ["dispatch.compile_fallbacks"]},
            )
        except Exception:  # lint: allow H501(alerting is best-effort; the fallback itself must proceed)
            pass
        return eager_fn()


# ----------------------------------------------------------------------
# entry points
# ----------------------------------------------------------------------
def materialize(expr: PendingExpr, out_sharding=None):
    """Compile-and-run a pending chain as one executable through the
    cache; returns the concrete jax.Array.  ``out_sharding`` (the array's
    canonical NamedSharding) pins the result placement the eager path
    used to establish with a per-op device_put."""
    if not _CACHE_ENABLED:
        nodes, leaves, _ = _linearize(expr)
        return _eval_nodes(nodes, leaves)
    with _launch("expr") as sp:
        nodes, leaves, _ = _linearize(expr)
        sp.attrs["ops"] = len(nodes)
        try:
            key = _program_key("expr", nodes, leaves, (out_sharding,))
        except TypeError:
            sp.attrs["fallback"] = True
            return _eval_nodes(nodes, leaves)
        return _compiled_or_fallback(
            sp, key, lambda: _build_program(nodes), leaves, len(nodes),
            lambda: _eval_nodes(nodes, leaves), out_sharding=out_sharding,
        )


def eager_apply(op, args: Sequence, kwargs: Optional[dict] = None):
    """Immediate op application through a cached executable (the slow
    binary path, helpers with concrete operands).  Falls back to a plain
    eager call when caching is off or the key is unhashable."""
    kwargs = kwargs or {}
    if not _CACHE_ENABLED:
        return op(*args, **kwargs)
    with _launch("apply", ops=1) as sp:
        try:
            key = ("apply", op, _kw_key(kwargs),
                   tuple(_leaf_spec(a) for a in args))
            hash(key)
        except TypeError:
            sp.attrs["fallback"] = True
            return op(*args, **kwargs)
        return _compiled_or_fallback(
            sp, key, lambda: (lambda *a: op(*a, **kwargs)), args, 1,
            lambda: op(*args, **kwargs),
        )


def chain_apply(op, x, kwargs: Optional[dict] = None, mask=None, lazy: bool = False):
    """Apply ``op(arr, **kwargs)`` where ``x`` is a pending chain or a
    concrete buffer: the chain, the optional pad-masking, and the op
    itself compile as ONE cached executable (the reduction/cum-op
    boundary of the fusion design).

    ``mask``: None, or ``(split, true_extent, neutral)`` — the padding
    rows are overwritten with ``neutral`` before ``op`` (the fused analog
    of ``DNDarray._masked``).

    ``lazy``: ``op`` is called as ``op(value, **kwargs)`` where
    ``value(take)`` evaluates the chain on ``take(leaf)`` of every leaf
    (``value(lambda leaf: leaf)`` is ``arr``).  For an op that reads a few
    rows of its input before the whole of it, or the whole of it again inside
    a loop: given the chain's value twice the compiler keeps it (a slice and
    a reduction of one elementwise result are not fused into both: a second
    table), given the leaves it computes the chain where it is read."""
    build = _build_lazy_program if lazy else _build_program
    if not _CACHE_ENABLED:
        return _eval_nodes(*_chain_nodes(op, x, kwargs, mask), build)
    with _launch("chain") as sp:
        nodes, leaves = _chain_nodes(op, x, kwargs, mask)
        sp.attrs["ops"] = len(nodes)
        try:
            key = _program_key("chain", nodes, leaves, (lazy,))
        except TypeError:
            sp.attrs["fallback"] = True
            return _eval_nodes(nodes, leaves, build)
        return _compiled_or_fallback(
            sp, key, lambda: build(nodes), leaves, len(nodes),
            lambda: _eval_nodes(nodes, leaves, build),
        )


def _chain_nodes(op, x, kwargs, mask):
    """``(nodes, leaves)`` of :func:`chain_apply`'s one program: the chain,
    the pad-masking, the op."""
    if isinstance(x, PendingExpr):
        nodes, leaves, _ = _linearize(x)
        root = (True, len(nodes) - 1)
    else:
        nodes, leaves = [], [x]
        root = (False, 0)
    if mask is not None:
        split, extent, neutral = mask
        nodes.append((_mask_pad,
                      {"split": int(split), "extent": int(extent), "neutral": neutral},
                      (root,)))
        root = (True, len(nodes) - 1)
    nodes.append((op, dict(kwargs or {}), (root,)))
    return nodes, leaves


# ----------------------------------------------------------------------
# donation-aware in-place paths
# ----------------------------------------------------------------------
def _probe_inner(obj):
    return sys.getrefcount(obj)


def _probe_outer(obj):
    # mirrors caller -> repad/cast_store -> _refcount_at_most -> getrefcount
    return _probe_inner(obj)


class _ProbeHolder:
    __slots__ = ("x", "args")


def _calibrate_plumbing() -> int:
    """Measured refcount of an object whose ONLY owner is one attribute,
    observed through the exact call shape the donation checks use
    (owner attribute + caller argument temp + two call frames +
    getrefcount's own argument).  Calibrated empirically because the
    per-frame reference cost depends on the CPython version's calling
    convention."""
    h = _ProbeHolder()
    h.x = object()
    return _probe_outer(h.x)


def _probe_leaf_site(dst, src):
    # mirrors cast_store's leaf check: one arg-slot tuple ref, the
    # deduped leaves list, the scan loop's binding, then the helper call
    leaves = [src.args[0]]
    for _i, leaf in enumerate(leaves):
        if leaf is dst:
            return _probe_inner(dst)
    return -1  # pragma: no cover


def _calibrate_leaf_site() -> int:
    """Refcount of a single-arg-slot, otherwise-unshared buffer at
    cast_store's leaf-donation check (owner attribute + plumbing + the
    arg-slot tuple + leaves list + loop binding)."""
    h = _ProbeHolder()
    h.x = object()
    h.args = (h.x,)
    return _probe_leaf_site(h.x, h)


#: refcount of a provably-unshared buffer at the check site
_RC_BASE = _calibrate_plumbing()
#: same, at the leaf-donation site with exactly one arg-slot reference
_RC_LEAF_BASE = _calibrate_leaf_site()


def _refcount_at_most(buf, extra: int = 0) -> bool:
    """CPython proof that ``buf`` has no holders beyond its owner
    attribute, the call plumbing (calibrated ``_RC_BASE``), and ``extra``
    known internal references (leaf lists, expression arg slots).  A
    shared backing array, a pending-expression leaf elsewhere, or a
    user-held ``larray_padded`` all push the count higher and suppress
    donation — the safe direction."""
    if not _DONATE_ENABLED or buf is None:
        return False
    try:
        return sys.getrefcount(buf) <= _RC_BASE + extra
    except Exception:  # lint: allow H501(non-CPython refcount probe -> donation off)
        return False


def _expr_private(root: PendingExpr, leaf_buf) -> bool:
    """Exact CPython proof that every chain node from which ``leaf_buf``
    is REACHABLE has no holder outside the chain itself (another
    DNDarray's pending attribute, a user variable).  Required before
    donating a LEAF buffer the chain consumes: a shared sub-expression
    that can reach the leaf would materialize later against the deleted
    buffer.  Nodes that cannot reach the leaf (e.g. the ``g * 0.1``
    sub-chain of ``w += g * 0.1``, still referenced by the dunder's
    temporary) are irrelevant and may be shared freely.

    Reference accounting per checked node: the ``order`` list entry +
    the loop variable + the getrefcount argument + one per arg-slot in
    parent nodes; the root additionally carries its owner's
    ``__pending`` attribute, the caller's ``src`` parameter, and this
    function's ``root`` parameter."""
    slots: dict = {}
    seen: set = set()
    order: list = []
    stack = [root]
    while stack:
        n = stack.pop()
        if id(n) in seen:
            continue
        seen.add(id(n))
        order.append(n)
        for a in n.args:
            if isinstance(a, PendingExpr):
                slots[id(a)] = slots.get(id(a), 0) + 1
                stack.append(a)

    reaches: dict = {}

    def _reaches(n: PendingExpr) -> bool:
        r = reaches.get(id(n))
        if r is None:
            reaches[id(n)] = False  # cycle guard (DAGs only, but cheap)
            r = any(
                (a is leaf_buf)
                or (isinstance(a, PendingExpr) and _reaches(a))
                for a in n.args
            )
            reaches[id(n)] = r
        return r

    for n in order:
        _reaches(n)
    for n in order:
        if not reaches.get(id(n)):
            continue
        if n is root:
            allowed = _RC_BASE + 2 + slots.get(id(n), 0)
        else:
            allowed = 3 + slots.get(id(n), 0)
        try:
            if sys.getrefcount(n) > allowed:
                return False
        except Exception:  # lint: allow H501(non-CPython refcount probe -> donation off)
            return False
    return True


def _refcount_leaf_at_most(buf, slots: int) -> bool:
    """Leaf-donation variant of :func:`_refcount_at_most`: compares
    against the calibrated leaf-site base (which already includes one
    arg-slot reference) plus any additional arg-slot references."""
    if not _DONATE_ENABLED or buf is None:
        return False
    try:
        return sys.getrefcount(buf) <= _RC_LEAF_BASE + (slots - 1)
    except Exception:  # lint: allow H501(non-CPython refcount probe -> donation off)
        return False


def repad(buf, old_slice, pad_widths, sharding, donate: bool = False):
    """Slice off the old padding, pad the new split axis, and place with
    the new canonical sharding — one cached executable (the body of
    ``resplit_``, which the eager path ran as slice + pad + device_put).

    ``old_slice``: None or ``(axis, true_extent)``; ``pad_widths``: None
    or the full jnp.pad width spec.  ``donate=True`` donates ``buf``
    (the array's dead backing buffer) when a refcount proof shows it is
    unshared.  Call with the buffer in argument position (no extra local
    bindings) so the calibrated refcount accounting holds."""
    if pad_widths is not None:
        pad_widths = tuple((int(a), int(b)) for a, b in pad_widths)
        if not any(b for _, b in pad_widths) and not any(a for a, _ in pad_widths):
            pad_widths = None
    if old_slice is not None:
        old_slice = (int(old_slice[0]), int(old_slice[1]))

    def build():
        def program(x):
            if old_slice is not None:
                ax, ext = old_slice
                x = jax.lax.slice_in_dim(x, 0, ext, axis=ax)
            if pad_widths is not None:
                x = jnp.pad(x, pad_widths)
            return x
        return program

    if not _CACHE_ENABLED:
        return jax.device_put(build()(buf), sharding)
    with _launch("repad", ops=1) as sp:
        donate = donate and _refcount_at_most(buf)
        try:
            key = ("repad", _leaf_spec(buf), old_slice, pad_widths, sharding, donate)
            hash(key)
        except TypeError:
            sp.attrs["fallback"] = True
            return jax.device_put(build()(buf), sharding)
        if not donate:
            return _compiled_or_fallback(
                sp, key, build, (buf,), 1,
                lambda: jax.device_put(build()(buf), sharding), out_sharding=sharding,
            )
        compiled, fresh = _get_compiled(key, build, donate_argnums=(0,), out_sharding=sharding)
        if fresh:
            _maybe_analyze(compiled, (buf,), key, donate_argnums=(0,))
        return _run(compiled, (buf,), 1, sp, donated=True, fresh=fresh, key=key)


def defer_store(dst_buf, src, dtype) -> Optional[PendingExpr]:
    """The chain an in-place target takes in place of its buffer, the
    cast to ``dtype`` folded in, where the store ``dst <- src`` can wait
    for the target's first reader; None where it has to run now.

    It can wait where ``src`` is a pending chain under the depth limit
    that reads ``dst_buf`` and no other buffer of its shape: the chain
    then keeps nothing alive that the store would have released (in
    ``x += y`` with a full-size ``y`` it would keep ``y``), and a later
    in-place operation on the same target grows the chain instead of
    making another pass over the buffer.  Nothing is launched here; the
    target runs the chain through :func:`cast_store` when it is read."""
    if (
        dst_buf is None
        or not fusion_enabled()
        or not isinstance(src, PendingExpr)
        or src.depth >= FUSION_DEPTH
    ):
        return None
    _, leaves, _ = _linearize(src)
    if [leaf is dst_buf for leaf in leaves if tuple(leaf.shape) == src.shape] != [True]:
        return None
    _C["deferred_stores"].inc()
    # no make_node: the shape is the chain's, and what marks a store is no
    # operation of the user's, so the chain is no deeper for it
    dtype = jnp.dtype(dtype)
    return PendingExpr(_stored, (src, scalar_leaf(1, dtype)), {"dtype": dtype}, src.shape,
                       dtype, src.depth, src.nops + 1)


def cast_store(dst_buf, src, dtype, out_sharding=None):
    """Compute ``src`` (pending chain or concrete buffer) cast to
    ``dtype`` as one cached executable, donating ``dst_buf`` — the
    ``out=`` / in-place target's about-to-die backing buffer — when a
    refcount proof shows it is unshared.

    Two donation shapes:

    * ``dst_buf`` IS a leaf of the chain (the ``a += b`` case): that leaf
      argument is donated, the classic ``donate_argnums`` aliasing.
    * ``dst_buf`` is not an operand (``mul(x, y, out=z)``): it is passed
      as an extra trailing argument, donated, so XLA may reuse its
      allocation for the output.

    Pass ``dst_buf`` in argument position (no extra local binding in the
    caller); the refcount proof compares against the calibrated call
    plumbing plus the leaf-list and arg-slot references when it is a
    leaf."""
    _C["stores"].inc()
    if not _CACHE_ENABLED:
        # no cache and so no program: the chain (fusion is off with the
        # cache, so one built before it went) and the cast, eagerly
        return _astype(materialize(src) if isinstance(src, PendingExpr) else src, dtype=dtype)
    with _launch("cast_store", store=True) as sp:
        if isinstance(src, PendingExpr):
            nodes, leaves, leaf_slots = _linearize(src)
            root = (True, len(nodes) - 1)
        else:
            nodes, leaves, leaf_slots = [], [src], {0: 1}
            root = (False, 0)
        nodes.append((_astype, {"dtype": dtype}, (root,)))
        # counted from the chain's own marks: no array and no node carries
        # a count of the stores that waited
        sp.attrs.update(ops=len(nodes), folded=sum(1 for op, _, _ in nodes if op is _stored))

        donate_ix = None
        trailing_dst = False
        if dst_buf is not None and _DONATE_ENABLED:
            for i, leaf in enumerate(leaves):
                if leaf is dst_buf:
                    # the `a += b` aliasing case: donating an OPERAND needs
                    # both proofs — the buffer itself is unshared (beyond
                    # the calibrated plumbing: the leaves-list entry, this
                    # loop's `leaf` binding, and one per expression arg-slot)
                    # AND the whole chain is private (no other DNDarray
                    # holds a sub-expression that would later materialize
                    # against the deleted buffer)
                    if (
                        isinstance(src, PendingExpr)
                        and _refcount_leaf_at_most(dst_buf, leaf_slots.get(i, 1))
                        and _expr_private(src, dst_buf)
                    ):
                        donate_ix = i
                    break
            else:
                # dst is not an operand: donated as an extra trailing
                # argument so XLA may reuse its allocation for the output
                if _refcount_at_most(dst_buf):
                    donate_ix = len(leaves)
                    trailing_dst = True

        if trailing_dst:
            n_real = len(leaves)
            inner = _build_program(nodes)

            def build():
                def program(*args):
                    return inner(*args[:n_real])
                return program

            leaves = leaves + [dst_buf]
        else:
            def build():
                return _build_program(nodes)

        try:
            key = _program_key(
                "cast_store", nodes, leaves,
                (out_sharding, donate_ix, trailing_dst),
            )
        except TypeError:
            sp.attrs["fallback"] = True
            return _eval_nodes(nodes, leaves if not trailing_dst else leaves[:-1])
        if donate_ix is None:
            return _compiled_or_fallback(
                sp, key, build, leaves, len(nodes),
                lambda: _eval_nodes(nodes, leaves), out_sharding=out_sharding,
            )
        compiled, fresh = _get_compiled(
            key, build, donate_argnums=(donate_ix,), out_sharding=out_sharding
        )
        if fresh:
            _maybe_analyze(compiled, leaves, key, donate_argnums=(donate_ix,))
        return _run(compiled, leaves, len(nodes), sp, donated=True, fresh=fresh, key=key)
