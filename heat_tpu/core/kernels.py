"""The one hand-written Pallas kernel of the array core: ``gram_syrk``.

The framework's compute path is XLA-compiled jnp; a Pallas kernel exists
only where a trace demands it, that is where a device trace shows XLA
streaming an operand more often than the algorithm needs and a benchmark
cell shows the kernel ahead.  ``gram_syrk`` is hSVD's Gram pass: XLA lowers
``x.T @ x`` as a generic dot with two operand streams, the kernel reads each
row tile of ``x`` once, at the rate the chip's memory streams (8.51 ms for
6.44 GB, 757 GB/s; ``hsvd-tallskinny.loop1`` times it, ``gram_syrk_ms``).
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
# are independent operand streams (no symmetric-rank-k form in the TPU
# backend).  This kernel tiles x over rows, reads each (rows, n) block once
# into VMEM, and accumulates blk.T @ blk into a VMEM-resident (n, n) output
# in explicit compensated bf16x3 (hi/lo split: the HIGH policy's three
# terms, ~3e-6 relative on G -- see linalg/svdtools._gram_precision for why
# that is enough for hsvd).  The three terms cost TWO MXU products a step:
# lo^T hi is the transpose of hi^T lo, an (n, n) transpose.
#
# What holds the kernel (chip runs, PR 30, one v5e, 12,582,912 x 128 f32,
# device time by the trace, twenty calls each):
#
#   rows a tile      1024    2048    4096    8192
#   three products  11.747   9.873   8.859   8.516 ms
#   two products    10.570   8.835   8.513   8.514
#   one product      9.409   8.512   8.512   8.513
#   no product       8.786   8.511   8.512   8.512
#
# The stream's own floor is 8.511 ms, 757 GB/s (not the published 819):
# deeper buffering and several DMAs a tile, written out by hand, read the
# same 8.51, and this Pallas refuses pl.Buffered(3).  A step's products
# cost about 0.3 us plus 0.62 ns a row (three) or 0.56 (two) against the
# DMA's 0.68 ns a row, so the products held the kernel at 2048 rows
# (9.87 ms, 653 GB/s) and a larger tile hides them: two products at 2 MiB
# reach the floor.  The tile is a constant of BYTES, so that the widths
# `syrk_supported` admits all fit the default fast-memory limit (the (n, n)
# output, the Kahan buffer and the products' results grow with n^2 beside
# the input's two buffers; 2048 rows did not compile at 384 and 512).
# ----------------------------------------------------------------------
_SYRK_TILE_BYTES = 2 * 1024 * 1024


def _syrk_rows(n: int) -> int:
    """Rows of one tile at width ``n``: ``_SYRK_TILE_BYTES`` of float32,
    rounded down to whole 128-row MXU passes (4096 rows at 128 columns,
    2048 at 256, 1280 at 384, 1024 at 512)."""
    return _SYRK_TILE_BYTES // (4 * n) // _LANES * _LANES


def syrk_supported(m: int, n: int, dtype) -> bool:
    """f32 tall blocks with lane-aligned width and at least one tile of
    rows; rows need no alignment (``gram_syrk`` takes the remainder past
    the last full tile through an XLA dot)."""
    return (
        jnp.dtype(dtype) == jnp.float32
        and n % _LANES == 0
        and 0 < n <= 512
        and m >= _syrk_rows(n)
    )


def _syrk_kernel(x_ref, o_ref, comp_ref):
    """Per-tile bf16x3 rank-k update with Kahan-compensated accumulation:
    a plain sequential f32 sum over the thousands of grid steps costs
    ~grid*eps (measured 1.5e-4 on G at 2^22 rows in 2048 steps); the
    compensation buffer brings it back to ~1e-6, and its (n, n) VPU work
    hides under the tile's DMA."""
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
    # (hi+lo)^T (hi+lo) dropping the lo^T lo term (below f32 eps); the two
    # cross terms are one product and its transpose, so ``hi`` is the only
    # operand that stands on the left of a row-contracting product
    cross = dot(hi, lo)
    contrib = dot(hi, hi) + cross + cross.T
    acc = o_ref[...]
    y = contrib - comp_ref[...]
    t = acc + y
    comp_ref[...] = (t - acc) - y
    o_ref[...] = t


def gram_syrk(x: jax.Array) -> jax.Array:
    """``x.T @ x`` for tall f32 ``x`` reading x once; the row remainder
    past the last full tile goes through a plain XLA dot and is added.
    The grid stops at the last full tile and ``x`` goes in whole: a slice
    ``x[:m0]`` in front of the custom call is a copy of all of it."""
    m, n = x.shape
    rows = _syrk_rows(n)
    steps = m // rows
    if steps == 0:  # public guard: short input is just the tail dot
        return jnp.matmul(x.T, x, precision=jax.lax.Precision.HIGH)
    call = pl.pallas_call(
        _syrk_kernel,
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        grid=(steps,),
        in_specs=[pl.BlockSpec((rows, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((n, n), lambda i: (0, 0)),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=_interpret(),
        name="gram_syrk",  # the device trace names the custom call by it (%gram_syrk.N)
    )
    g = call(x)
    if steps * rows < m:
        tail = x[steps * rows :]
        g = g + jnp.matmul(tail.T, tail, precision=jax.lax.Precision.HIGH)
    return g
