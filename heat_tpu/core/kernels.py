"""Pallas TPU kernels for HBM-bound hot loops.

The framework's compute path is XLA-compiled jnp; these kernels exist only
where fusing beats what GSPMD/XLA emit.  First case: the KMeans Lloyd
iteration (the reference's cdist ring + argmin + one-hot-matmul update,
cluster/kmeans.py + spatial/distance.py:209).  XLA runs it as several
passes over the point set (distance matmul, argmin, one-hot segment sums)
plus (N, k) intermediates; the kernel below makes it ONE pass: each tile
of points is read once from HBM and its distances, assignments, centroid
partial sums, counts and inertia are all produced in VMEM.

Layout is the whole trick.  Points are tall-and-skinny (f ≈ 16 features),
and a (TILE, f) VMEM tile wastes 1 - f/128 of every lane row.  So the
kernel packs R = 128//f points into each 128-lane row — the (N, f) array
is *viewed* as (N/R, 128) with zero data movement — and computes all R
points' cluster distances with one MXU matmul against a block-diagonal
``kron(I_R, centers.T)`` matrix.  Per-point argmin is an in-group circular
lane-roll fold, and the centroid sums come out of a second packed matmul
whose (R*kp, 128) result is unscrambled outside the kernel.  Every lane
does real work and HBM traffic is exactly one read of x per iteration.

On non-TPU backends the same kernel runs through the Pallas interpreter,
so the test suite (virtual CPU mesh) exercises the identical code path.

**Measured outcome (v5e, 2^24 x 16 f32, k=8)**: the kernel is *correct*
but VPU-bound — the in-lane argmin folds cost ~25 full-tile VPU ops per
tile against a ~1.3 us/tile DMA floor, landing at ~73 ms/iteration, while
the trimmed two-pass XLA program (cluster/kmeans.py `_lloyd_update`)
runs at ~3.5 ms.  On this chip the VPU:HBM ratio leaves a budget of only
~5 VPU ops per element-lane, so single-pass fusion cannot pay for an
exact packed argmin.  The kernel is therefore OPT-IN
(``HEAT_TPU_LLOYD_KERNEL=1``): kept as the correctness-tested skeleton
for hardware with a different compute:bandwidth balance, and as the
honest record of why the default stays with XLA — exactly the
"Pallas only if profiling demands" policy the design docs call for.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

__all__ = [
    "lloyd_update",
    "lloyd_supported",
    "LLOYD_KERNEL",
    "gram_syrk",
    "syrk_supported",
]

import os

#: opt-in switch for the fused kernel (see module docstring for why the
#: default is the XLA path)
LLOYD_KERNEL = os.environ.get("HEAT_TPU_LLOYD_KERNEL", "0") == "1"

_LANES = 128
_TILE_POINTS = 16384  # points per grid step; G = _TILE_POINTS // R lane rows


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _next_pow2(k: int) -> int:
    p = 1
    while p < k:
        p *= 2
    return p


def _slots_per_point(f: int, k: int) -> int:
    """Cluster slots per point: next_pow2(k), then widened until the packed
    cluster space r*kp is lane-aligned (Mosaic's dynamic_rotate rejects
    vectors narrower than one 128-lane row)."""
    r = _LANES // f
    kp = _next_pow2(k)
    while r * kp < _LANES:
        kp *= 2
    return kp


def lloyd_supported(f: int, k: int) -> bool:
    """Packed-kernel applicability: whole points per lane row (f | 128) and
    the packed cluster space within a small multiple of the lane width."""
    if f <= 0 or k <= 0 or _LANES % f != 0:
        return False
    r = _LANES // f
    return r * _slots_per_point(f, k) <= 512


def _roll_right(x: jax.Array, t) -> jax.Array:
    """Circular right-shift along lanes: out[l] = x[l - t] (t may be traced)."""
    if _interpret():
        return jnp.roll(x, t, 1)
    return pltpu.roll(x, t, 1)


def _group_shift(x: jax.Array, t, kp: int, slot: jax.Array) -> jax.Array:
    """out[l] = x[group(l)*kp + (slot(l)+t) % kp] — circular shift inside
    each kp-lane group, built from two whole-row rolls and a select.
    ``t`` may be a traced int in [1, kp)."""
    cols = x.shape[1]
    left = _roll_right(x, cols - t)  # out[l] = x[l + t]
    right = _roll_right(x, kp - t)  # out[l] = x[l - (kp - t)]
    return jnp.where(slot < kp - t, left, right)


def _lloyd_kernel(f: int, kp: int, nt_ref, x_ref, ck_ref, c2_ref, accs_ref, accc_ref, acci_ref):
    """One packed tile of the fused Lloyd iteration.

    R = 128//f points per lane row; G lane rows per tile.  Inputs:
    x_ref (G, 128) — R points' features per row; ck_ref (128, R*kp) —
    kron(I_R, centers.T), zero-padded from k to kp columns per point slot;
    c2_ref (1, R*kp) — |c_j|^2 per slot, +inf in pad slots.  Outputs
    (accumulated over the sequential grid): accs_ref (R*kp, 128) —
    onehot.T @ x, unscrambled outside; accc_ref (1, R*kp) — member counts
    per slot; acci_ref (1, 128) — inertia partials (sum |x|^2 over the
    x-lane space plus sum of per-point min distances over the slot space,
    both reduced to scalars outside).
    """
    r = _LANES // f
    g = x_ref.shape[0]
    i = pl.program_id(0)

    xb = x_ref[:].astype(jnp.float32)  # (G, 128)

    # zero out invalid points (shard padding / ragged final tile): lane l
    # holds a feature of point (base + lane//f)
    xlane = jax.lax.broadcasted_iota(jnp.int32, (g, _LANES), 1)
    xrow = (i * g + jax.lax.broadcasted_iota(jnp.int32, (g, _LANES), 0)) * r
    x_valid = (xrow + xlane // f) < nt_ref[0]
    xb = jnp.where(x_valid, xb, 0.0)

    # all R points x all k centers in one MXU pass; HIGHEST keeps f32
    # mantissas (the default bf16 passes would put ~2^-9 relative error on
    # the centroid sums).  The kernel is DMA-bound, the extra passes are free.
    xc = jnp.dot(
        xb, ck_ref[:], preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST
    )  # (G, R*kp)
    half = c2_ref[0][None, :] - 2.0 * xc  # |c|^2 - 2 x.c ; +inf in pad slots

    cols = r * kp
    slot = jax.lax.broadcasted_iota(jnp.int32, (g, cols), 1) % kp

    # per-point argmin with first-index tie-break, entirely in lane space:
    # fold the group minimum, then the smallest slot attaining it.
    # fori_loop (not an unrolled python loop) keeps the live-buffer count
    # O(1); unrolled folds blow the Mosaic VMEM stack at useful tile sizes.
    vmin = jax.lax.fori_loop(
        1, kp, lambda t, vm: jnp.minimum(vm, _group_shift(half, t, kp, slot)), half
    )
    jsel = jnp.where(half == vmin, slot, kp)
    jmin = jax.lax.fori_loop(
        1, kp, lambda t, jm: jnp.minimum(jm, _group_shift(jsel, t, kp, slot)), jsel
    )

    # one-hot over valid points; slot column c belongs to point base+c//kp
    crow = (i * g + jax.lax.broadcasted_iota(jnp.int32, (g, cols), 0)) * r
    clane = jax.lax.broadcasted_iota(jnp.int32, (g, cols), 1)
    c_valid = (crow + clane // kp) < nt_ref[0]
    oh = ((slot == jmin) & c_valid).astype(jnp.float32)  # (G, R*kp)

    # inertia partials: sum|x|^2 (x already zeroed when invalid) plus the
    # per-point min half-distance, counted once per point at slot 0
    x2_part = jnp.sum(xb * xb, axis=0)  # (128,)
    v_part = jnp.sum(jnp.where((slot == 0) & c_valid, vmin, 0.0), axis=0)  # (cols,)

    @pl.when(i == 0)
    def _():
        accs_ref[:] = jnp.zeros_like(accs_ref)
        accc_ref[:] = jnp.zeros_like(accc_ref)
        acci_ref[:] = jnp.zeros_like(acci_ref)

    accs_ref[:] += jnp.dot(
        oh.T, xb, preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST
    )
    accc_ref[0, :] += jnp.sum(oh, axis=0)
    acci_ref[0, :] += x2_part + _pad_lanes(v_part, _LANES)


def _pad_lanes(v: jax.Array, lanes: int) -> jax.Array:
    """Fold a (cols,) vector into (lanes,) by summing lane-width chunks
    (cols is a multiple or divisor of lanes by construction).  Static
    slicing only — lane->sublane reshapes don't lower well in Mosaic."""
    cols = v.shape[0]
    if cols == lanes:
        return v
    if cols > lanes:
        acc = v[:lanes]
        for i in range(1, cols // lanes):
            acc = acc + v[i * lanes : (i + 1) * lanes]
        return acc
    return jnp.pad(v, (0, lanes - cols))


def _build_operands(centers: jax.Array, f: int, k: int, kp: int):
    """Host-side constants: the block-diagonal kron matrix and slot |c|^2."""
    r = _LANES // f
    c32 = centers.astype(jnp.float32)
    ck = jnp.zeros((_LANES, r * kp), jnp.float32)
    for ri in range(r):
        ck = ck.at[ri * f : (ri + 1) * f, ri * kp : ri * kp + k].set(c32.T)
    c2 = jnp.sum(c32 * c32, axis=1)
    c2slot = jnp.full((r * kp,), jnp.inf, jnp.float32)
    for ri in range(r):
        c2slot = c2slot.at[ri * kp : ri * kp + k].set(c2)
    return ck, c2slot[None, :]


def _unscramble(accs, accc, acci, f: int, k: int, kp: int):
    """(R*kp, 128) packed sums -> (k, f) sums, (k,) counts, scalar inertia."""
    r = _LANES // f
    sums = jnp.zeros((k, f), jnp.float32)
    counts = jnp.zeros((k,), jnp.float32)
    for ri in range(r):
        sums = sums + accs[ri * kp : ri * kp + k, ri * f : (ri + 1) * f]
        counts = counts + accc[0, ri * kp : ri * kp + k]
    inertia = jnp.sum(acci)
    return sums, counts, inertia


def _lloyd_acc(xp: jax.Array, centers: jax.Array, n_true) -> tuple:
    """Fused pass over one device's rows.  ``n_true`` may be traced.
    Returns (sums (k,f), counts (k,), inertia scalar) as float32."""
    n, f = xp.shape
    k = centers.shape[0]
    kp = _slots_per_point(f, k)
    r = _LANES // f
    # tile G lane-rows: bounded in points AND in lane-rows (a (G, 128) f32
    # buffer is G*512 bytes and ~8 of them are live in the kernel)
    g = min(max(_TILE_POINTS // r, 8), 2048)

    rows_packed = n // r if n % r == 0 else n // r + 1
    xv = xp.reshape(n // r, _LANES) if n % r == 0 else None
    if xv is None:
        # pad to a whole number of packed rows (rare: shard sizes are
        # padded to mesh multiples well above R)
        pad = rows_packed * r - n
        xv = jnp.pad(xp, ((0, pad), (0, 0))).reshape(rows_packed, _LANES)

    ck, c2 = _build_operands(centers, f, k, kp)
    nt = jnp.asarray(n_true, jnp.int32).reshape(1)
    grid = (pl.cdiv(rows_packed, g),)
    kernel = functools.partial(_lloyd_kernel, f, kp)
    cols = r * kp
    out_shapes = (
        jax.ShapeDtypeStruct((cols, _LANES), jnp.float32),
        jax.ShapeDtypeStruct((1, cols), jnp.float32),
        jax.ShapeDtypeStruct((1, _LANES), jnp.float32),
    )
    in_specs = [
        pl.BlockSpec((g, _LANES), lambda i, *_: (i, 0)),
        pl.BlockSpec((_LANES, cols), lambda i, *_: (0, 0)),
        pl.BlockSpec((1, cols), lambda i, *_: (0, 0)),
    ]
    out_specs = (
        pl.BlockSpec((cols, _LANES), lambda i, *_: (0, 0)),
        pl.BlockSpec((1, cols), lambda i, *_: (0, 0)),
        pl.BlockSpec((1, _LANES), lambda i, *_: (0, 0)),
    )
    if not _interpret():
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs, out_specs=out_specs
        )
        accs, accc, acci = pl.pallas_call(
            kernel, out_shape=out_shapes, grid_spec=grid_spec, name="lloyd_update"
        )(nt, xv, ck, c2)
    else:
        accs, accc, acci = pl.pallas_call(
            kernel,
            out_shape=out_shapes,
            grid=grid,
            in_specs=[pl.BlockSpec((1,), lambda i, *_: (0,))] + in_specs,
            out_specs=out_specs,
            interpret=True,
            name="lloyd_update",
        )(nt, xv, ck, c2)
    return _unscramble(accs, accc, acci, f, k, kp)


def _postprocess(sums, counts, inertia, centers):
    new = jnp.where(
        counts[:, None] > 0,
        sums / jnp.maximum(counts, 1.0)[:, None],
        centers.astype(jnp.float32),
    ).astype(centers.dtype)
    shift = jnp.sum((new.astype(jnp.float32) - centers.astype(jnp.float32)) ** 2)
    return new, shift, inertia


@functools.partial(jax.jit, static_argnames=("n_true",))
def _lloyd_single(xp, centers, n_true):
    sums, counts, inertia = _lloyd_acc(xp, centers, n_true)
    return _postprocess(sums, counts, inertia, centers)


@functools.cache
def _lloyd_sharded(mesh, axis_name: str, n_true: int):
    """Jitted multi-device step: per-shard fused pass, psum of the tiny
    (k, f+2)-sized accumulators, replicated postprocess."""

    def body(xs, c):
        rank = jax.lax.axis_index(axis_name)
        local_rows = xs.shape[0]
        nt_local = jnp.clip(n_true - rank * local_rows, 0, local_rows)
        sums, counts, inertia = _lloyd_acc(xs, c, nt_local)
        return jax.lax.psum((sums, counts, inertia), axis_name)

    @jax.jit
    def step(xp, centers):
        sums, counts, inertia = shard_map(
            body,
            mesh=mesh,
            in_specs=(P(axis_name), P()),
            out_specs=(P(), P(), P()),
            # pallas_call outputs don't carry vma metadata for the new
            # shard_map varying-axes check
            check_vma=False,
        )(xp, centers)
        return _postprocess(sums, counts, inertia, centers)

    return step


def lloyd_update(x, centers: jax.Array):
    """One fused Lloyd iteration on a DNDarray of points.

    Returns ``(new_centers, shift, inertia)``; does NOT compute labels (the
    fit loop only needs them after convergence — assignment stays a
    separate cheap pass in the caller).
    """
    xp = x.larray_padded
    if x.split == 0 and x.comm.size > 1:
        step = _lloyd_sharded(x.comm.mesh, x.comm.axis_name, x.shape[0])
        return step(xp, centers)
    return _lloyd_single(xp, centers, x.shape[0])


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
