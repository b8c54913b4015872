"""The one hand-written Pallas kernel of the array core: ``gram_syrk``.

The framework's compute path is XLA-compiled jnp; a Pallas kernel exists
only where a trace demands it, that is where a device trace shows XLA
streaming an operand more often than the algorithm needs and a benchmark
cell shows the kernel ahead.  ``gram_syrk`` is hSVD's Gram pass: XLA lowers
``x.T @ x`` as a generic dot with two operand streams, the kernel reads each
row tile of ``x`` once (``hsvd-tallskinny.loop1`` times it, ``gram_syrk_ms``).
On non-TPU backends it runs through the Pallas interpreter, so the tests on
the virtual CPU mesh exercise the same code.

What outlives the Lloyd kernel that stood here until PR 29: one pass over
points packed 128 // f to a lane row is VPU-bound on v5e (its in-lane argmin),
74.74 ms an iteration at 2^24 x 16 against the XLA fit loop's 2.16 (chip run, PR 29).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "gram_syrk",
    "syrk_supported",
]

_LANES = 128


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ----------------------------------------------------------------------
# syrk: G = x.T @ x with ONE HBM read of x (hsvd's Gram pass).
#
# XLA lowers the Gram matmul as a generic dot whose lhs (x.T) and rhs (x)
# are independent operand streams — the r5 profile measured it at
# ~5.7 ms for (2^22, 128) f32 where one read of x at stream bandwidth is
# ~3.3 ms (no syrk/symmetric-rank-k optimization in the TPU backend).
# This kernel tiles x over rows, reads each (TILE, n) block once into
# VMEM, and accumulates blk.T @ blk into a VMEM-resident (n, n) output
# with explicit compensated bf16x3 passes (hi/lo split, three MXU dots:
# the HIGH policy's arithmetic, ~1e-6 relative on G — see
# linalg/svdtools._gram_precision for why that is enough for hsvd).
# ----------------------------------------------------------------------
_SYRK_TILE = 2048


def syrk_supported(m: int, n: int, dtype) -> bool:
    """f32 tall blocks with lane-aligned width; rows need no alignment
    (the caller splits off the row remainder)."""
    return (
        jnp.dtype(dtype) == jnp.float32
        and n % _LANES == 0
        and 0 < n <= 512
        and m >= _SYRK_TILE
    )


def _syrk_kernel(x_ref, o_ref, comp_ref):
    """Per-tile bf16x3 rank-k update with Kahan-compensated accumulation:
    a plain sequential f32 sum over the ~2k grid steps costs ~grid*eps
    (measured 1.5e-4 on G at 2^22 rows); the compensation buffer brings
    it back to ~1e-6 for free (VPU work against a DMA-bound kernel)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        comp_ref[...] = jnp.zeros_like(comp_ref)

    blk = x_ref[...]
    hi = blk.astype(jnp.bfloat16)
    lo = (blk - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    dims = (((0,), (0,)), ((), ()))
    dot = lambda a, b: jax.lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32
    )
    # (hi+lo)^T (hi+lo) dropping the lo^T lo term (below f32 eps)
    contrib = dot(hi, hi) + dot(hi, lo) + dot(lo, hi)
    acc = o_ref[...]
    y = contrib - comp_ref[...]
    t = acc + y
    comp_ref[...] = (t - acc) - y
    o_ref[...] = t


def gram_syrk(x: jax.Array) -> jax.Array:
    """``x.T @ x`` for tall f32 ``x`` reading x once; the row remainder
    past the last full tile goes through a plain XLA dot and is added."""
    m, n = x.shape
    m0 = (m // _SYRK_TILE) * _SYRK_TILE
    if m0 == 0:  # public guard: short input is just the tail dot
        return jnp.matmul(x.T, x, precision=jax.lax.Precision.HIGH)
    head = x[:m0]
    grid = (m0 // _SYRK_TILE,)
    call = pl.pallas_call(
        _syrk_kernel,
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        grid=grid,
        in_specs=[pl.BlockSpec((_SYRK_TILE, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((n, n), lambda i: (0, 0)),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=_interpret(),
        name="gram_syrk",  # the device trace names the custom call by it (%gram_syrk.N)
    )
    g = call(head)
    if m0 < m:
        tail = x[m0:]
        g = g + jnp.matmul(tail.T, tail, precision=jax.lax.Precision.HIGH)
    return g
