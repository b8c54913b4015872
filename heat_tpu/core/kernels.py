"""The hand-written Pallas kernels of the array core: ``gram_syrk``, the
two bodies of a selection by group, ``grouped_digit_counts`` and
``grouped_neighbours``, and ``cd_sweeps``, Lasso's descent on its normal
equations (its section at the end has the readings).

The framework's compute path is XLA-compiled jnp; a Pallas kernel exists
only where a trace demands it, that is where a device trace shows XLA
streaming an operand more often than the algorithm needs, or spending
several times the memory's time on operations a kernel can do without, and
a benchmark cell shows the kernel ahead.  ``grouped_digit_counts`` is one
counting pass of KMedians' grouped selection and ``grouped_neighbours`` its
last pass (their section below has the readings; ``kmedians-spheres3d.loop1``
times them).  ``gram_syrk`` is hSVD's Gram pass: XLA lowers
``x.T @ x`` as a generic dot with two operand streams, the kernel reads each
row tile of ``x`` once, at the rate the chip's memory streams (8.51 ms for
6.44 GB, 757 GB/s; ``hsvd-tallskinny.loop1`` times it, ``gram_syrk_ms``);
given ``y`` it is Lasso's one read of its table, the Gram and the moments
from the same tiles (its second body, PR 40; ``lasso-1e7x128.loop1``).
On non-TPU backends it runs through the Pallas interpreter, so the tests on
the virtual CPU mesh exercise the same code.

What outlives the Lloyd kernel that stood here until PR 29: one pass over
points packed 128 // f to a lane row is VPU-bound on v5e (its in-lane argmin),
74.74 ms an iteration at 2^24 x 16 against the XLA fit loop's 2.16 (chip run, PR 29).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "cd_sweeps",
    "cd_supported",
    "gram_syrk",
    "grouped_digit_counts",
    "grouped_neighbours",
    "pack_columns",
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
#
# The moments' body (PR 40, `_syrk_moments_kernel`, Lasso's): with ``y`` the
# same step also sums, from the shifted tile it holds, the columns, their
# products with ``y`` and their squares, float32 on the VPU, so that Lasso
# reads its table once where it read it twice.  ``y`` comes 4,096 targets a
# tile as a row of lanes (a (m, 1) column's own bytes) and is put on the
# sublanes by a transpose in the kernel (`_along_rows`).  Step 0 (chip run,
# PR 40, call 1; 10^7 x 128 f32, device time by the trace, ten calls each):
#
#   gram_syrk, the shift, no y                       6.765 ms  (757 GB/s)
#   the moments' body, y by a batched transpose      6.847     (+1.2%)
#   the same, y by a transpose and lane broadcasts   6.945     (+2.7%)
#   the same, no broadcast of y at all (not a sum)   6.787
#   XLA's moments' loop alone, the parent's second read  7.128
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


def _kahan_add(acc_ref, comp_ref, contrib):
    """``acc_ref += contrib``, the rounding of each step kept in ``comp_ref``
    and taken from the next: a plain sequential f32 sum over the thousands
    of grid steps costs ~grid*eps (measured 1.5e-4 on G at 2^22 rows in 2048
    steps); the compensation brings it back to ~1e-6, and its VPU work hides
    under the tile's DMA."""
    acc = acc_ref[...]
    y = contrib - comp_ref[...]
    t = acc + y
    comp_ref[...] = (t - acc) - y
    acc_ref[...] = t


def _bf16x3_gram(blk):
    """One tile's ``blk^T blk`` in compensated bf16x3: (hi+lo)^T (hi+lo)
    dropping the lo^T lo term (below f32 eps); the two cross terms are one
    product and its transpose, so ``hi`` is the only operand that stands on
    the left of a row-contracting product."""
    hi = blk.astype(jnp.bfloat16)
    lo = (blk - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    dims = (((0,), (0,)), ((), ()))
    dot = lambda a, b: jax.lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32
    )
    cross = dot(hi, lo)
    return dot(hi, hi) + cross + cross.T


def _syrk_kernel(*refs, shifted: bool = False):
    """Per-tile bf16x3 rank-k update with Kahan-compensated accumulation
    (`_kahan_add`).  ``shifted``: a row of the columns' shifts comes second
    and is taken from the tile first."""
    x_ref, o_ref, comp_ref = refs[0], refs[-2], refs[-1]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        comp_ref[...] = jnp.zeros_like(comp_ref)

    blk = x_ref[...] - refs[1][...] if shifted else x_ref[...]
    _kahan_add(o_ref, comp_ref, _bf16x3_gram(blk))


def _along_rows(yt, n: int):
    """A tile's targets, ``(rows / 128, 128)``, as ``(rows, n)``: row ``r``
    holds target ``r`` in every lane.  Each row of 128 targets is
    put on the sublanes by a transpose of it broadcast down a (128, 128)
    block; wider tiles take the same block again along the lanes."""
    k = yt.shape[0]
    down = jnp.broadcast_to(yt[:, None, :], (k, _LANES, _LANES))
    along = jnp.swapaxes(down, 1, 2).reshape(k * _LANES, _LANES)
    return along if n == _LANES else jnp.concatenate([along] * (n // _LANES), axis=1)


def _syrk_moments_kernel(x_ref, shift_ref, y_ref, cy_ref, o_ref, m_ref, comp_ref, m_comp_ref):
    """`_syrk_kernel`'s shifted step and, from the same shifted tile, its
    moments: the columns' sums, their products with the targets less
    ``cy``, their sums of squares and the targets' sum, each as (8, n)
    partial sums a row of sublanes, all float32 on the VPU and compensated
    across the grid as the Gram is.  ``y_ref`` holds the tile's targets in a
    row of lanes (`_along_rows` puts them on the sublanes), ``cy_ref``
    (scalar memory) their shift."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        for ref in (o_ref, m_ref, comp_ref, m_comp_ref):
            ref[...] = jnp.zeros_like(ref)

    blk = x_ref[...] - shift_ref[...]
    rows, n = blk.shape
    yt = (y_ref[...] - cy_ref[0, 0]).reshape(rows // _LANES, _LANES)
    yb = _along_rows(yt, n)
    by8 = lambda a: jnp.sum(a.reshape(rows // 8, 8, n), axis=0)
    contrib = jnp.stack([by8(blk), by8(blk * yb), by8(blk * blk), by8(yb)])
    _kahan_add(m_ref, m_comp_ref, contrib)
    _kahan_add(o_ref, comp_ref, _bf16x3_gram(blk))


def gram_syrk(x: jax.Array, shift=None, y=None, y_shift=None):
    """``x.T @ x`` for tall f32 ``x`` reading x once; the row remainder
    past the last full tile goes through a plain XLA dot and is added.
    The grid stops at the last full tile and ``x`` goes in whole: a slice
    ``x[:m0]`` in front of the custom call is a copy of all of it.
    ``shift`` (``(n,)``, optional): the Gram of ``x - shift``, the shift
    taken from every tile as it is read (``x - shift`` in front of the
    custom call would be a second table); without it the call and its
    compiled text are what they were.

    ``y`` (``(m,)`` or ``(m, 1)``, optional): the moments too, from the
    tiles the Gram reads: ``(G, s1, bxy, q, sy)``, with ``xc = x - shift``
    (zeros if None) and ``yc = y - y_shift`` (0 if None), ``xc^T xc``,
    ``xc``'s column sums, ``xc^T yc``, ``xc``'s column sums of squares and
    ``yc``'s sum, the last four float32 sums on the VPU
    (`_syrk_moments_kernel`).  ``y`` goes in as ONE row of lanes, the bytes
    a ``(m, 1)`` column lies in: as ``(m, 1)`` rows of lanes it would be a
    second table, each target padded to 128.  Without ``y`` nothing of this
    is in the program."""
    m, n = x.shape
    rows = _syrk_rows(n)
    steps = m // rows
    if y is not None:
        return _syrk_moments(x, jnp.zeros((n,), x.dtype) if shift is None else shift, y,
                             0.0 if y_shift is None else y_shift, rows, steps)
    shifted = shift is not None
    moved = (lambda a: a - shift[None, :]) if shifted else (lambda a: a)
    if steps == 0:  # public guard: short input is just the tail dot
        return jnp.matmul(moved(x).T, moved(x), precision=jax.lax.Precision.HIGH)
    call = pl.pallas_call(
        functools.partial(_syrk_kernel, shifted=True) if shifted else _syrk_kernel,
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        grid=(steps,),
        in_specs=[pl.BlockSpec((rows, n), lambda i: (i, 0))] + [pl.BlockSpec((1, n), lambda i: (0, 0))] * shifted,
        out_specs=pl.BlockSpec((n, n), lambda i: (0, 0)),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=_interpret(),
        name="gram_syrk",  # the device trace names the custom call by it (%gram_syrk.N)
    )
    g = call(x, shift[None, :]) if shifted else call(x)
    if steps * rows < m:
        tail = moved(x[steps * rows :])
        g = g + jnp.matmul(tail.T, tail, precision=jax.lax.Precision.HIGH)
    return g


def _syrk_moments(x, shift, y, y_shift, rows: int, steps: int):
    """`gram_syrk` with ``y``: the moments' body over the whole tiles; the
    rows past the last one, their Gram and their moments, in XLA."""
    m, n = x.shape
    y = y.astype(x.dtype).reshape(1, m)  # as a (m, 1) column lies: the same bytes
    y_shift = jnp.asarray(y_shift, x.dtype)
    done = steps * rows
    tail, yt = x[done:] - shift[None, :], y[0, done:] - y_shift
    g = jnp.matmul(tail.T, tail, precision=jax.lax.Precision.HIGH)
    s1, bxy, q, sy = jnp.sum(tail, axis=0), jnp.sum(tail * yt[:, None], axis=0), jnp.sum(tail * tail, axis=0), jnp.sum(yt)
    if steps:
        g_, mo = pl.pallas_call(
            _syrk_moments_kernel,
            out_shape=(jax.ShapeDtypeStruct((n, n), jnp.float32), jax.ShapeDtypeStruct((4, 8, n), jnp.float32)),
            grid=(steps,),
            in_specs=[pl.BlockSpec((rows, n), lambda i: (i, 0)), pl.BlockSpec((1, n), lambda i: (0, 0)),
                      pl.BlockSpec((1, rows), lambda i: (0, i)),
                      pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=(pl.BlockSpec((n, n), lambda i: (0, 0)), pl.BlockSpec((4, 8, n), lambda i: (0, 0, 0))),
            scratch_shapes=[pltpu.VMEM((n, n), jnp.float32), pltpu.VMEM((4, 8, n), jnp.float32)],
            interpret=_interpret(),
            name="gram_syrk_moments",  # holds ``gram_syrk``: the benchmark's readers find the pass by that
        )(x, shift[None, :], y, y_shift.reshape(1, 1))
        mo = jnp.sum(mo, axis=1)
        g, s1, bxy, q, sy = g + g_, s1 + mo[0], bxy + mo[1], q + mo[2], sy + mo[3, 0]
    return g, s1, bxy, q, sy


# ----------------------------------------------------------------------
# a selection by group (KMedians' per-cluster medians): one counting pass
# and the neighbours' pass, all groups in ONE read of the values each.
#
# As XLA writes the pass on the table as it lies (rows x 3, the long axis
# minor, a quarter of every register padding: one compare, one mask, one
# convert and one add for each group's each pivot) a value costs some 55
# operations at 2 bits and four groups, and the pass reads 28.3 ms at
# 2^28 x 3 where the memory needs 7 (chip run, PERF.md, PR 37).  The kernel
# does what a fusion cannot: it asks only whether the value lies in ITS
# group's range and which digit it holds there, and adds ``1 << 8 * digit``
# to its group's packed counters, four digits' counts a 32-bit lane,
# unpacked once a block: some 25 operations a value.  It reads the values
# column by column from a copy that fills every register (`pack_columns`);
# on the table itself (columns on the sublanes, three of eight used, the
# groups' bits broadcast along the lanes in every step) the same kernel read
# 51.8 ms a pass (chip run, PR 37).
#
# The selection's last pass, the smallest key above each group's median's,
# is a second body on the same operands, block and step
# (`grouped_neighbours`; PR 38), so that a grouped selection reads the copy
# through 17 calls of a kernel and no fusion reads it: as one XLA `reduce`
# of twelve operands over the copy and four masks of the labels the pass
# read 14.4 + 3.25 ms.  What holds the bodies (chip run, PR 38, step 0,
# 2^28 x 3 float32 and four groups, ms a pass; the plain read of copy and
# labels as an XLA fusion 6.99):
#
#   operations a value    20      23      25 (counts)   31
#   accumulators          16      16      16            32 registers
#   ms a pass             6.10    6.33    6.25          8.01
#
# Up to some 25 operations the memory's 6.1 ms hide them (704 GB/s); at
# 31, a count of each group's NaNs in accumulators of its own, they bind.
# So the NaNs cost two operations, not eleven: a NaN is made the smallest
# key there is, and its group's minimum tells of it (the 23 above).
# ----------------------------------------------------------------------
#: the packed copy's rows are this long, and one step of the kernel's inner
#: loop holds eight of them in registers
COUNT_LANES = 512
#: rows of the packed copy a block of the kernel holds: 32 steps a column,
#: under the 255 a packed counter, a byte, can count (a lane counts once a step)
COUNT_ROWS = 256


def pack_columns(x: jax.Array) -> jax.Array:
    """``x`` (rows x columns) column by column, each column as whole rows of
    ``COUNT_LANES`` values, zeros behind the last: ``(columns, R, COUNT_LANES)``
    with ``R`` a whole number of the kernel's blocks.  On the chip every
    register of this copy is full, where the table's own are a quarter
    padding at three columns; a caller that reads its values many times
    makes it once."""
    rows, f = x.shape
    whole = COUNT_ROWS * COUNT_LANES
    padded = -(-rows // whole) * whole
    return jnp.pad(x.T, ((0, 0), (0, padded - rows))).reshape(f, padded // COUNT_LANES, COUNT_LANES)


def _count_kernel(shift_ref, phi_ref, x_ref, lab_ref, out_ref, *, groups, bits):
    f = x_ref.shape[0]
    kdt, nbits = (jnp.int64, 64) if x_ref.dtype == jnp.float64 else (jnp.int32, 32)
    some = (8, COUNT_LANES)  # what one step holds of a column
    by_shift, by_bits = jnp.full(some, shift_ref[0], kdt), jnp.full(some, bits, kdt)  # a shift's second operand has the first's shape

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    for c in range(f):
        def step(j, accs, c=c):
            at = pl.multiple_of(j * 8, 8)
            s = jax.lax.bitcast_convert_type(x_ref[c, pl.ds(at, 8), :], kdt)
            # the bits in the order of the values, to be read without a sign: all
            # flipped for a negative value, the top one for the others
            u = s ^ ((s >> (nbits - 1)) | kdt(-1 << (nbits - 1)))
            lab = lab_ref[pl.ds(at, 8), :]
            member = [lab == g for g in range(groups)]
            phi = jnp.full(some, phi_ref[(groups - 1) * f + c], kdt)
            for g in range(groups - 2, -1, -1):
                phi = jnp.where(member[g], phi_ref[g * f + c], phi)
            low = jax.lax.shift_right_logical(u, by_shift)  # the digit and what lies above it
            inc = jax.lax.shift_left(jnp.int32(1), ((low & kdt((1 << bits) - 1)) << 3).astype(jnp.int32))
            inc = jnp.where(jax.lax.shift_right_logical(low, by_bits) == phi, inc, 0)  # in its group's range
            return tuple(acc + jnp.where(m, inc, 0) for acc, m in zip(accs, member))

        accs = jax.lax.fori_loop(0, x_ref.shape[1] // 8, step, tuple(jnp.zeros(some, jnp.int32) for _ in range(groups)))
        for g in range(groups):
            for b in range(1 << bits):
                out_ref[g, b, c] += jax.lax.shift_right_logical(accs[g], jnp.int32(8 * b)) & 0xFF


def _neighbours_kernel(pivot_ref, x_ref, lab_ref, above_ref, *, groups):
    f = x_ref.shape[0]
    kdt, nbits = (jnp.int64, 64) if x_ref.dtype == jnp.float64 else (jnp.int32, 32)
    some = (8, COUNT_LANES)  # what one step holds of a column
    top, bottom = kdt(jnp.iinfo(kdt).max), kdt(jnp.iinfo(kdt).min)
    inf = kdt(((1 << jnp.finfo(x_ref.dtype).nexp) - 1) << jnp.finfo(x_ref.dtype).nmant)  # the bits of +inf: a NaN's lie above, the sign apart

    @pl.when(pl.program_id(0) == 0)
    def _():
        above_ref[...] = jnp.full_like(above_ref, top)

    for c in range(f):
        def step(j, lows, c=c):
            at = pl.multiple_of(j * 8, 8)
            s = jax.lax.bitcast_convert_type(x_ref[c, pl.ds(at, 8), :], kdt)
            key = s ^ ((s >> (nbits - 1)) & top)  # the signed order key: the lower bits flipped for a negative value
            lab = lab_ref[pl.ds(at, 8), :]
            member = [lab == g for g in range(groups)]
            pivot = jnp.full(some, pivot_ref[(groups - 1) * f + c], kdt)
            for g in range(groups - 2, -1, -1):
                pivot = jnp.where(member[g], pivot_ref[g * f + c], pivot)
            beyond = jnp.where(key > pivot, key, top)  # above ITS group's key, or the minimum's identity
            beyond = jnp.where((s & top) > inf, bottom, beyond)  # a NaN: under every number's key, so the minimum tells of it
            return tuple(jnp.minimum(low, jnp.where(m, beyond, top)) for low, m in zip(lows, member))

        lows = jax.lax.fori_loop(0, x_ref.shape[1] // 8, step, tuple(jnp.full(some, top, kdt) for _ in range(groups)))
        for g in range(groups):
            above_ref[g, c] = jnp.minimum(above_ref[g, c], lows[g])


def _grouped_call(kernel, name: str, out_shapes, scalars, packed: jax.Array, labels: jax.Array):
    """One pass of ``kernel`` over `pack_columns`' copy and its labels, a block
    of ``COUNT_ROWS`` rows a grid step; ``scalars`` are prefetched, the outputs
    (``out_shapes``: a few registers a group and column each) stay where they
    are from the first block to the last.  The device trace names the custom
    call by ``name`` (``%<name>.N``)."""
    f, rows, lanes = packed.shape
    assert labels.shape == (rows, lanes) and lanes == COUNT_LANES and rows % COUNT_ROWS == 0
    return pl.pallas_call(
        kernel,
        out_shape=out_shapes,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(rows // COUNT_ROWS,),
            in_specs=[pl.BlockSpec((f, COUNT_ROWS, lanes), lambda i, *_: (0, i, 0)),
                      pl.BlockSpec((COUNT_ROWS, lanes), lambda i, *_: (i, 0))],
            out_specs=[pl.BlockSpec(o.shape, lambda i, *_, n=len(o.shape): (0,) * n) for o in out_shapes]),
        interpret=_interpret(),
        name=name,
    )(*scalars, packed, labels)


def grouped_digit_counts(packed: jax.Array, labels: jax.Array, prefix: jax.Array, shift, bits: int, groups: int) -> jax.Array:
    """For each of ``groups`` groups and each column of ``packed``
    (`pack_columns`' copy: columns x R x ``COUNT_LANES``, float32; float64
    through the interpreter), how many of the group's values hold each value
    of the ``bits``-wide digit at ``shift`` in their order key, among those
    whose key agrees with the group's settled ``prefix`` (groups x columns,
    the unsigned key's bits above the digit in place, zeros below) in every
    bit above the digit: ``int32[groups, 2**bits, columns]``.  ``labels``
    (R x ``COUNT_LANES``, int32) name each value's group, any other number
    none (so the zeros behind the last row).  ``bits`` is 1 or 2: four
    digits' counters share a lane.  ``shift`` may be a device value: a
    caller's loop over the passes is then ONE call of the kernel, one name in
    the device trace.

    ONE read of the values and of the labels, whatever ``groups``; nothing of
    their size is written."""
    f, lanes = packed.shape[0], packed.shape[2]
    assert 1 <= bits <= 2
    kdt = jnp.int64 if packed.dtype == jnp.float64 else jnp.int32
    shift = jnp.asarray(shift, jnp.int32).reshape(1)
    # the bits above the digit, in two shifts: the first pass's digit is the top one, and no shift takes all the bits
    above = jax.lax.shift_right_logical(jax.lax.bitcast_convert_type(prefix, kdt), jnp.broadcast_to(shift.astype(kdt), prefix.shape))
    phi = jax.lax.shift_right_logical(above, jnp.full(prefix.shape, bits, kdt)).reshape(groups * f)
    (counts,) = _grouped_call(functools.partial(_count_kernel, groups=groups, bits=bits), "kmedians_count",
                              [jax.ShapeDtypeStruct((groups, 1 << bits, f, 8, lanes), jnp.int32)], (shift, phi), packed, labels)
    return jnp.sum(counts, axis=(-2, -1))


def grouped_neighbours(packed: jax.Array, labels: jax.Array, pivots: jax.Array, groups: int) -> jax.Array:
    """For each of ``groups`` groups and each column of ``packed``, over the
    group's values alone (``packed``, ``labels`` as `grouped_digit_counts`
    takes them): the smallest order key strictly above the group's ``pivots``
    entry (groups x columns, the SIGNED key, as the result), the key type's
    largest value where none lies above, and its SMALLEST value, which is no
    number's key, where a NaN is among the group's values: a minimum over
    several devices' results keeps that evidence.

    The counting passes' operands, block and step in a body of its own: ONE
    read of the values and of the labels, nothing of their size written."""
    f, lanes = packed.shape[0], packed.shape[2]
    kdt = jnp.int64 if packed.dtype == jnp.float64 else jnp.int32
    (above,) = _grouped_call(functools.partial(_neighbours_kernel, groups=groups), "kmedians_neighbours",
                             [jax.ShapeDtypeStruct((groups, f, 8, lanes), kdt)], (pivots.reshape(groups * f),), packed, labels)
    return jnp.min(above, axis=(-2, -1))


# ----------------------------------------------------------------------
# cyclic coordinate descent on the normal equations (Lasso's sweeps), the
# whole of it ONE kernel: G, 66 KB at 129 coordinates, lies in the core's
# vector memory and the 12,900 dependent turns of a fit run on it there.
#
# As XLA operations a turn is a launch or several (chip runs, PR 39, one
# v5e, 129 coordinates, us a turn): the row of G sliced out and the
# threshold on scalars, eighteen operations, 3.00; the same with the
# threshold taken of the whole vector, four, 0.79; ALL coordinates' rho a
# turn (G theta: the 128 it does not use are cheaper than a dynamic slice),
# three, 0.24.  No form is under two fusions a turn, and a traced window of
# 4 s and 206 fits of 25,800 events overran the profiler's 2^22 events: the
# trace held 161 of them and every share read off it was wrong.  The kernel
# is one event a fit, and 0.155 us a turn (2.00 ms a fit of 100 sweeps).
#
# The equations may stand in a SHIFTED frame (Lasso's: the columns taken
# about a shift near their means, so that float32 holds the spreads and
# not the means' squares): the unknowns are then ``t = (u, theta)`` with
# ``u = theta_0 + shift . theta`` (less the targets' shift), and a step of
# coordinate ``j`` with the intercept held drags ``u`` along by ``shift_j``
# times the step.  That is the kernel's ``drag``; all zeros, it is plain
# cyclic descent.
# ----------------------------------------------------------------------
_CD_MAX = 1024  # coordinates: A and the turns' last values, (1024, 1024) float32 each, are 8 MiB of the core's memory


def cd_supported(m: int, dtype) -> bool:
    """float32 normal equations small enough to lie in vector memory whole."""
    return jnp.dtype(dtype) == jnp.float32 and 0 < m <= _CD_MAX


def _cd_kernel(tol_ref, of_ref, off_ref, rows_ref, t_ref, it_ref, moved_ref, last_ref, *, m: int, max_iter: int):
    """At most ``max_iter`` sweeps of ``m`` turns from ``t0``, ended by a
    sweep that moves no coordinate by ``tol``.  ``of_ref`` (scalar memory)
    holds, a row each, ``b``, the thresholds, the columns' sums of squares,
    the drags and ``t0``; ``off_ref`` is ``A`` less those sums of squares on
    its diagonal; ``rows_ref`` holds ``t0`` and the row that reads the
    intercept off ``t`` (1 at place 0, less the drags); ``last_ref`` keeps
    every coordinate's last value in all places of a row of its own, so that
    a turn's step is known in place 0 without a second reduction."""
    t0, reads_intercept = rows_ref[0:1, :], rows_ref[1:2, :]
    place = jax.lax.broadcasted_iota(jnp.int32, t0.shape, 1)
    tol = tol_ref[0, 0]

    def remember(j, _):
        last_ref[pl.ds(j, 1), :] = jnp.full(t0.shape, of_ref[4, j])
        return 0

    jax.lax.fori_loop(0, m, remember, 0)

    def turn(j, t):
        # rho_j = b_j - sum_k A_jk t_k + col_sq_j t_j, the same in every place; the threshold; place j takes it and
        # place 0 the drag's share of the step
        rho = jnp.full(t.shape, of_ref[0, j]) - jnp.sum(off_ref[pl.ds(j, 1), :] * t)
        new = jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - of_ref[1, j], 0.0) / of_ref[2, j]
        step = new - last_ref[pl.ds(j, 1), :]
        last_ref[pl.ds(j, 1), :] = new
        return jnp.where(place == j, new, t) + jnp.where(place == 0, of_ref[3, j] * step, 0.0)

    def cond(carry):
        _, it, moved = carry
        return jnp.logical_and(it < max_iter, moved >= tol)

    def body(carry):
        t, it, _ = carry
        new = jax.lax.fori_loop(0, m, turn, t)
        # a sweep's largest move, the intercept's own in place 0 (not u's: u moves with every dragged step)
        intercept_moved = jnp.abs(jnp.sum(reads_intercept * new) - jnp.sum(reads_intercept * t))
        return new, it + 1, jnp.max(jnp.where(place == 0, intercept_moved, jnp.abs(new - t)))

    t, it, moved = jax.lax.while_loop(cond, body, (t0, jnp.int32(0), jnp.float32(jnp.inf)))
    t_ref[...] = t
    it_ref[0, 0] = it
    moved_ref[0, 0] = moved


def cd_sweeps(A: jax.Array, b: jax.Array, lams: jax.Array, col_sq: jax.Array, drag: jax.Array, tol, t0: jax.Array,
              max_iter: int):
    """Cyclic coordinate descent, the coordinates in order, at most
    ``max_iter`` sweeps from ``t0``, ended by a sweep that moves no
    coordinate by ``tol``: ``(t, sweeps run, the last sweep's largest
    move)``.  Coordinate ``j``'s turn: ``rho = b_j - sum_k A_jk t_k +
    col_sq_j t_j``, ``t_j`` becomes ``soft(rho, lams_j) / col_sq_j``, and
    ``t_0`` moves by ``drag_j`` times ``t_j``'s step (``drag_0`` is 0).  With
    ``A = G`` symmetric, ``col_sq`` its diagonal and no drag that is
    coordinate descent on ``1/2 t^T G t - b^T t + sum_j lams_j |t_j|``; with
    a drag, ``t_0`` stands for ``intercept + drag . t`` and what a sweep
    moved is read of the intercept itself."""
    m = A.shape[0]
    rows_of, lanes_of = -(-m // 8) * 8, -(-m // _LANES) * _LANES
    col_sq = jnp.maximum(col_sq, 1e-30)
    off = jnp.pad(A - jnp.diag(col_sq), ((0, rows_of - m), (0, lanes_of - m)))
    rows = jnp.zeros((8, lanes_of), A.dtype).at[0, :m].set(t0).at[1, :m].set(jnp.zeros((m,), A.dtype).at[0].set(1) - drag)
    t, it, moved = pl.pallas_call(
        functools.partial(_cd_kernel, m=m, max_iter=max_iter),
        out_shape=(jax.ShapeDtypeStruct((1, lanes_of), A.dtype), jax.ShapeDtypeStruct((1, 1), jnp.int32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM), pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM), pl.BlockSpec(memory_space=pltpu.SMEM),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        scratch_shapes=[pltpu.VMEM((rows_of, lanes_of), A.dtype)],
        interpret=_interpret(),
        name="lasso_cd",  # the device trace names the custom call by it (%lasso_cd.N)
    )(jnp.asarray(tol, jnp.float32).reshape(1, 1), jnp.stack([b, lams, col_sq, drag, t0]), off, rows)
    return t[0, :m], it[0, 0], moved[0, 0]
