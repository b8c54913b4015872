"""Timing monitor for the continuous-benchmark suite.

The reference instruments its cb functions with the external ``perun``
energy/runtime monitor (benchmarks/cb/linalg.py:4, setup.py extras
``cb=perun``).  perun is MPI-bound; the TPU-native stand-in measures
wall time around a fully-synchronized call and emits one JSON line per
benchmark — the same shape the round driver's bench.py reports.

Synchronization is a device->host fetch of one element of the result:
the device executes in order, so the fetch returns only after the
measured call completed.  The fetch adds one host-device round trip to
every measurement; the runner reports that floor so dashboards can
subtract it.
"""

from __future__ import annotations

import functools
import json
import time
from typing import Any

import jax
import numpy as np

RESULTS = []


def _sync(obj: Any) -> None:
    """Force execution of everything reachable from ``obj`` (one scalar
    fetch per distinct jax array)."""
    if hasattr(obj, "_val") and hasattr(obj, "_comp"):  # DCSX sparse planes
        _sync(obj._val)
    elif hasattr(obj, "larray_padded"):
        _sync(obj.larray_padded)
    elif isinstance(obj, jax.Array):
        # fetch ONE element lazily — ravel()/reshape would dispatch a
        # full-size on-device copy inside the timed region
        np.asarray(jax.device_get(obj[(0,) * obj.ndim]))
    elif isinstance(obj, (tuple, list)):
        for o in obj:
            _sync(o)
    elif isinstance(obj, dict):
        for o in obj.values():
            _sync(o)


def sync_floor() -> float:
    """Measured cost of the scalar-fetch synchronization itself."""
    f = jax.jit(lambda x: x + 1.0)
    z = jax.numpy.zeros(())
    _sync(f(z))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        _sync(f(z))
        best = min(best, time.perf_counter() - t0)
    return best


def monitor():
    """Decorator mirroring perun's ``@monitor()`` (benchmarks/cb usage)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            _sync(out)
            elapsed = time.perf_counter() - t0
            record = {"bench": fn.__name__, "seconds": round(elapsed, 6)}
            RESULTS.append(record)
            print(json.dumps(record), flush=True)
            return out

        return wrapper

    return deco
