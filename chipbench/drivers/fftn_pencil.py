"""Driver of the distributed 3-D FFT cells.

The timed entry is the public ``ht.fft.fftn(x)`` on a float32 cube split
along axis 0 over the chips, ended when the result is ready on every chip.
Everything below ``solve`` is the benchmark's own yardstick and imports
nothing of the program: the data generator, the plain reference (the
transform by its definition, as real matrix products), the comparison, the
lower-precision control, the faults that ``correct`` has to refuse and the
work model.

A process with one device never enters the pencil (the program transforms
a whole array with ``jnp.fft``), so a rehearsal on the CPU needs as many
devices as the configuration has chips:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 python3 -m chipbench.selftest
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import seeded

HIGHEST = jax.lax.Precision.HIGHEST
# The planted plane waves, (frequency as a function of the side, amplitude,
# phase): ``a cos(2 pi k.r / side + phase)`` puts ``side^3 a / 2 e^(i phase)``
# at ``k``, some 10^4 times the root-mean-square of the noise's spectrum at
# side 1024, so a relative error of the transform shows there in absolute
# terms.  No ``k`` is its own mirror image.
WAVES = (
    (lambda s: (s // 8, 3 * s // 16, s // 4), 1.0, 0.3),
    (lambda s: (s // 2 + 5, 1, s // 3), 0.5, 1.1),
    (lambda s: (3, s - 2, s // 5), 0.25, 2.0),
)
# Columns of the cube a reference pass takes at a time: the products'
# operands and results of one block are what the pass holds beside its
# input and output planes (at side 1024, 16 columns: 67 MB a plane).
REF_BLOCK = 16
# The altered answer: the plane of the first wave's peak times 1 + 1.5 times
# ``peak_rel``'s limit (configs/fftn-pencil-1024.json).
ALTERED_BY = 1.5e-4


def _phase(side: int, k):
    """``2 pi (k.r mod side) / side`` on the cube's grid: the integer product
    is reduced before it becomes a float, so the angle is good to the last
    bits at every side."""
    i, j, l = (jax.lax.broadcasted_iota(jnp.int32, (side,) * 3, d) for d in range(3))
    return ((k[0] * i + k[1] * j + k[2] * l) % side).astype(jnp.float32) * np.float32(2.0 * math.pi / side)


def _field(key, side: int):
    """A standard normal field and the planted waves.  Every operation is
    elementwise on the grid, and the random bits are partitionable
    (``jax_threefry_partitionable``, JAX's default), so under ``jit`` with a
    sharded result each chip makes its own slab and no other."""
    x = jax.random.normal(key, (side,) * 3, jnp.float32)
    for freq, amp, phase in WAVES:
        x = x + np.float32(amp) * jnp.cos(_phase(side, freq(side)) + np.float32(phase))
    return x


def build(cfg: dict, seed: int, rows=None) -> dict:
    """``rows`` is the cube's side at rehearsal."""
    import heat_tpu as ht

    side, chips = rows or cfg["shape"][0], cfg["chips"]
    if len(jax.devices()) < chips:
        raise SystemExit(f"chipbench: {cfg['entry']} split over {chips} chips needs {chips} devices and JAX reports "
                         f"{len(jax.devices())}; rehearse under XLA_FLAGS=--xla_force_host_platform_device_count={chips}")
    sharding = ht.get_comm().sharding(cfg["split"])
    x = jax.jit(partial(_field, side=side), out_shardings=sharding)(seeded.key(seed))
    return {"x": ht.core.dndarray.DNDarray.from_dense(x, cfg["split"]), "side": side, "split": cfg["split"],
            "notes": {"side": side, "chips": len(sharding.device_set),
                      "waves": [[list(f(side)), a, p] for f, a, p in WAVES]}}


def solve(state: dict) -> dict:
    """One solve: the public call, ended when the result is ready on every chip."""
    import heat_tpu as ht

    y = ht.fft.fftn(state["x"])
    out = {"y": y.larray_padded}
    jax.block_until_ready(out)
    out.update(split=y.split, shape=tuple(y.shape))
    return out


def work(cfg: dict, rows=None) -> dict:
    """The least one solve demands of A CHIP, from the shapes alone.  With
    ``n = side^3`` points over ``chips`` chips: ``bytes`` = the chip's float32
    slab read once and its complex64 slab of the spectrum written once,
    ``(4 + 8) n / chips`` (at 1024^3 over four: 1.07 + 2.15 = 3.22 GB);
    ``operations`` = ``5 n log2(n) / chips``, the usual count of a complex
    transform of ``n`` points (4.03e10 a chip; a real input could do with
    half, and the matrix form XLA uses does more: neither is the least or is
    counted).  Beside them, for the readers of the communication layer:
    ``chips``, and ``ici_bytes``, what a chip must send for the two
    transposes at the stated dtypes: ``(chips - 1) / chips`` of its float32
    slab going in and of its complex64 slab coming back (0.81 + 1.61 = 2.42
    GB).  ``solve_roofline_pct`` reads ``bytes`` and ``operations`` against
    one chip's peaks (its busy time is already the chips' mean); peaks.json
    holds no interconnect peak, so the share reads low in this cell."""
    side, chips = rows or cfg["shape"][0], cfg["chips"]
    n = side ** 3
    return {"bytes": 12 * n // chips, "operations": int(5 * n * math.log2(n)) // chips, "chips": chips,
            "ici_bytes": 12 * n // chips * (chips - 1) // chips}


# ---------------------------------------------------------------- reference
def _dft_matrices(side: int):
    """``F[k, n] = exp(-2 pi i k n / side) = C - i S``, built in float64 on
    the host from the exactly reduced ``k n mod side``."""
    k = np.arange(side, dtype=np.int64)
    ang = 2.0 * np.pi * ((k[:, None] * k[None, :]) % side) / side
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def _product(spec: str, m, a, low: bool):
    """One real matrix product of the reference: float32 at ``highest``, or
    for the control one bfloat16 pass with float32 accumulation."""
    if low:
        return jnp.einsum(spec, m.astype(jnp.bfloat16), a.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    return jnp.einsum(spec, m, a, precision=HIGHEST)


@partial(jax.jit, static_argnames=("axis", "low", "sharding"))
def _axis_pass(re, im, c, s, axis: int, low: bool, sharding=None):
    """``(C - i S)`` applied along ``axis`` of the planes ``re + i im``
    (``im`` may be None: a real input): ``C re + S im`` and ``C im - S re``,
    a block of columns of another axis at a time."""
    spec = ("kn,nab->kab", "kn,anb->akb", "kn,abn->abk")[axis]
    along = 1 if axis == 2 else 2  # the blocks' axis: never the transformed one, never axis 0 (the split one)
    width = math.gcd(REF_BLOCK, re.shape[along])

    def pin(a):
        return a if sharding is None else jax.lax.with_sharding_constraint(a, sharding)

    def body(i, planes):
        r = jax.lax.dynamic_slice_in_dim(re, i * width, width, along)
        out_re, out_im = _product(spec, c, r, low), -_product(spec, s, r, low)
        if im is not None:
            m = jax.lax.dynamic_slice_in_dim(im, i * width, width, along)
            out_re, out_im = out_re + _product(spec, s, m, low), out_im + _product(spec, c, m, low)
        return tuple(pin(jax.lax.dynamic_update_slice_in_dim(p, o, i * width, along))
                     for p, o in zip(planes, (out_re, out_im)))

    zeros = pin(jnp.zeros(re.shape, jnp.float32))
    return jax.lax.fori_loop(0, re.shape[along] // width, body, (zeros, zeros))


def dft3(re, im=None, low: bool = False, inverse: bool = False, sharding=None):
    """The transform by its definition, ``Y = F0 . F1 . F2 x``, over the
    three axes of the planes ``re + i im``, last axis first; the inverse is
    the conjugate matrices and ``1 / n``.  Float32 planes; calls no ``fft`` routine."""
    side = re.shape[0]
    re, im = jnp.asarray(re, jnp.float32), None if im is None else jnp.asarray(im, jnp.float32)
    c, s = (jnp.asarray(m) for m in _dft_matrices(side))
    if inverse:
        s = -s
    for axis in (2, 1, 0):
        re, im = _axis_pass(re, im, c, s, axis=axis, low=low, sharding=sharding)
    return (re / side ** 3, im / side ** 3) if inverse else (re, im)


def _planes_of(state: dict, low: bool) -> dict:
    x = state["x"].larray_padded
    re, im = dft3(x, low=low, sharding=x.sharding)
    return {"re": re, "im": im}


def reference(state: dict) -> dict:
    return _planes_of(state, low=False)


# --------------------------------------------------------------- comparison
@jax.jit
def _spectrum_stats(y, re, im):
    """Per plane of axis 0: the squared error, the reference's squared norm
    and the largest squared error (the planes' sums are added in float64 on
    the host)."""
    err = (jnp.real(y) - re) ** 2 + (jnp.imag(y) - im) ** 2
    return jnp.sum(err, axis=(1, 2)), jnp.sum(re * re + im * im, axis=(1, 2)), jnp.max(err, axis=(1, 2))


@partial(jax.jit, static_argnames=("k",))
def _coefficient(y, x, k):
    """The coefficient at ``k`` as the result has it (picked by a mask: no
    index reaches into another chip's slab) and by the defining sum over the
    field, ``sum x exp(-i phase)``, in partial sums per plane."""
    side = x.shape[0]
    i, j, l = (jax.lax.broadcasted_iota(jnp.int32, y.shape, d) for d in range(3))
    got = jnp.sum(jnp.where((i == k[0]) & (j == k[1]) & (l == k[2]), y, 0))
    ang = _phase(side, k)
    return got, jnp.sum(x * jnp.cos(ang), axis=(1, 2)), -jnp.sum(x * jnp.sin(ang), axis=(1, 2))


def compare(state: dict, out: dict, ref: dict) -> dict:
    """Numbers of the last timed solve against the reference, over every
    coefficient: ``spec_rel_l2`` ``|Y - Y_ref|_2 / |Y_ref|_2``;
    ``spec_max_err`` the largest ``|Y - Y_ref|`` over the root-mean-square
    of ``Y_ref`` (one wrong plane shows here); ``peak_rel`` the planted
    waves' coefficients against the defining sum over the field, the largest
    relative distance; ``split_gap`` 0 when the result's split, shape and
    placement are the input's."""
    x, y = state["x"].larray_padded, out["y"]
    side = state["side"]
    err, norm, worst = (np.asarray(a, np.float64) for a in _spectrum_stats(y, ref["re"], ref["im"]))
    peak_rel = 0.0
    for freq, _, _ in WAVES:
        got, want_re, want_im = _coefficient(y, x, freq(side))
        want = complex(np.asarray(want_re, np.float64).sum(), np.asarray(want_im, np.float64).sum())
        peak_rel = max(peak_rel, abs(complex(got) - want) / abs(want))
    same = (out["split"] == state["split"] and tuple(out["shape"]) == tuple(x.shape) and y.shape == x.shape
            and y.sharding.is_equivalent_to(x.sharding, y.ndim))
    return {"spec_rel_l2": math.sqrt(err.sum() / norm.sum()),
            "spec_max_err": math.sqrt(worst.max() / (norm.sum() / side ** 3)),
            "peak_rel": peak_rel, "split_gap": 0.0 if same else 1.0}


# ------------------------------------------------------------------ control
def control(state: dict) -> dict:
    """The reference's mathematics put in the program's place, with each
    product in one bfloat16 pass: what `correct` has to refuse."""
    planes = _planes_of(state, low=True)
    x = state["x"].larray_padded
    y = jax.jit(jax.lax.complex, out_shardings=x.sharding)(planes["re"], planes["im"])
    return {"y": y, "split": state["split"], "shape": tuple(x.shape)}


# ------------------------------------------------------------------- faults
def _edited(a, fn):
    """``fn(a)``, placed as ``a`` is."""
    return jax.jit(fn, out_shardings=a.sharding)(a)


def faults() -> dict:
    """Faults planted under the timed path, {name: (module, attribute,
    maker)}: ``maker(original)`` takes the attribute's place.  All three sit
    on ONE seam, ``fft._transform_padded``, the function ``fftn`` hands the
    padded array to and takes the padded result from, and only wrap what
    goes in and what comes out: whatever the program does between the two
    may change without moving them."""
    import importlib

    program = importlib.import_module("heat_tpu.fft.fft")

    def along0(a, keep):  # a mask over the planes of axis 0, to multiply with
        return keep(jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)).astype(a.dtype)

    def half(original):  # the second half of the planes zeroed going in
        def f(blk, *a, **kw):
            return original(_edited(blk, lambda b: b * along0(b, lambda i: i < b.shape[0] // 2)), *a, **kw)
        return f

    def altered(original):  # one plane of the result, the first wave's, times 1 + ALTERED_BY
        def f(blk, *a, **kw):
            k0 = WAVES[0][0](blk.shape[1])[0]
            return _edited(original(blk, *a, **kw), lambda o: o * (1.0 + ALTERED_BY * along0(o, lambda i: i == k0)))
        return f

    def swapped(original):  # two chips' blocks of the result exchanged: what a wrong all_to_all layout does
        def f(blk, *a, **kw):
            out = original(blk, *a, **kw)
            mesh, spec = out.sharding.mesh, out.sharding.spec
            n = mesh.devices.size
            pair = (1, 2) if n > 2 else (0, n - 1)
            perm = [(i, pair[1] if i == pair[0] else pair[0] if i == pair[1] else i) for i in range(n)]
            return jax.jit(jax.shard_map(lambda b: jax.lax.ppermute(b, mesh.axis_names[0], perm), mesh=mesh,
                                         in_specs=spec, out_specs=spec))(out)
        return f

    return {"half": (program, "_transform_padded", half), "altered": (program, "_transform_padded", altered),
            "swapped": (program, "_transform_padded", swapped)}
