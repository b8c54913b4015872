"""Driver of the fixed-rank hierarchical SVD cells.

The timed entry is the public ``ht.linalg.hsvd_rank(A, rank, compute_sv=True)``
on a split-0 float32 ``DNDarray``.  Everything below ``solve`` is the
benchmark's own yardstick and imports nothing of the program: the data
generator, the plain reference (a row-blocked Gram at ``highest``, its
eigen-decomposition in float64 on the host), the comparison, the
lower-precision control, the faults that ``correct`` has to refuse and the
work model.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import seeded

HIGHEST = jax.lax.Precision.HIGHEST
# A float32 dot on the MXU loses part of a long sum of squares: at `highest` the
# Gram's diagonal read 2.2e-5 low over 262,144 rows, 8e-4 low over 12.6M, and
# within 1.2e-6 (mean +3e-7) over 16,384 (my chip run, PR 24, against sums of
# squares taken 256 rows at a time).  So the reference sums short blocks on
# the device and the blocks in float64 on the host.
REF_BLOCK_ROWS = 16384


@partial(jax.jit, static_argnames=("rows", "cols", "nb"))
def _make(key, scales, rows: int, cols: int, nb: int):
    """normal x column scales, written block by block into one buffer, so
    that the peak is the array plus one block's temporaries."""
    bs = rows // nb

    def body(i, buf):
        blk = jax.random.normal(jax.random.fold_in(key, i), (bs, cols), jnp.float32) * scales
        return jax.lax.dynamic_update_slice(buf, blk, (i * bs, 0))

    return jax.lax.fori_loop(0, nb, body, jnp.zeros((rows, cols), jnp.float32))


def build(cfg: dict, seed: int, rows=None) -> dict:
    import heat_tpu as ht

    rows = rows or cfg["rows"]
    cols = cfg["cols"]
    nb = seeded.blocks(rows, 48)
    scales = jnp.asarray(np.geomspace(1.0, 1e-3, cols), jnp.float32)
    a = _make(seeded.key(seed), scales, rows, cols, nb)
    A = ht.core.dndarray.DNDarray.from_dense(a, cfg["split"])
    del a
    return {"A": A, "rank": cfg["rank"], "nb": nb, "ref_nb": seeded.blocks(rows, max(1, rows // REF_BLOCK_ROWS)),
            "notes": {"rows": rows, "cols": cols, "blocks": nb}}


def solve(state: dict) -> dict:
    """One solve: the public call, ended when every output a user reads is ready."""
    import heat_tpu as ht

    U, S, V, rel_err = ht.linalg.hsvd_rank(state["A"], state["rank"], compute_sv=True)
    out = {"U": U.larray_padded, "S": S.larray_padded, "V": V.larray_padded, "rel_err": rel_err}
    jax.block_until_ready(out)
    return out


def work(cfg: dict, rows=None) -> dict:
    """The least one solve demands of the chip, from the shapes alone: one
    read of A and the write of U; a symmetric Gram and the projection."""
    m, n, k = rows or cfg["rows"], cfg["cols"], cfg["rank"]
    return {"bytes": m * n * 4 + m * k * 4, "operations": m * n * n + 2 * m * n * k}


# ---------------------------------------------------------------- reference
@partial(jax.jit, static_argnames=("nb", "low"))
def _gram_blocks(a, nb: int, low: bool):
    """Per-block Grams (nb, n, n): float32 at ``highest``, or for the control
    one bfloat16 pass with float32 accumulation."""
    bs = a.shape[0] // nb

    def one(i):
        blk = jax.lax.dynamic_slice_in_dim(a, i * bs, bs, 0)
        if low:
            blk = blk.astype(jnp.bfloat16)
            return jnp.matmul(blk.T, blk, preferred_element_type=jnp.float32)
        return jnp.matmul(blk.T, blk, precision=HIGHEST)

    return jax.lax.map(one, jnp.arange(nb))


def reference(state: dict) -> dict:
    a = state["A"].larray_padded
    k = state["rank"]
    g = np.asarray(_gram_blocks(a, state["ref_nb"], False), np.float64).sum(axis=0)
    lam, v = np.linalg.eigh(g)
    lam, v = np.maximum(lam[::-1], 0.0), v[:, ::-1]
    return {"S": np.sqrt(lam[:k]), "V": v[:, :k],
            "rel_err": float(np.sqrt(lam[k:].sum() / lam.sum()))}


@partial(jax.jit, static_argnames=("nb",))
def _u_dist2(a, u, v_ref, inv_s_ref, sign, nb: int):
    """Per column, over row blocks: the squared distance between the
    sign-aligned U and the reference's A V_ref / s_ref."""
    bs = a.shape[0] // nb

    def body(i, d2):
        ab = jax.lax.dynamic_slice_in_dim(a, i * bs, bs, 0)
        ub = jax.lax.dynamic_slice_in_dim(u, i * bs, bs, 0)
        diff = ub * sign[None, :] - jnp.matmul(ab, v_ref, precision=HIGHEST) * inv_s_ref[None, :]
        return d2 + jnp.sum(diff * diff, axis=0)

    return jax.lax.fori_loop(0, nb, body, jnp.zeros((u.shape[1],), jnp.float32))


def compare(state: dict, out: dict, ref: dict) -> dict:
    """Numbers of the last timed solve against the reference; columns of
    unit norm, so the distances are relative."""
    s = np.asarray(out["S"], np.float64)
    v = np.asarray(out["V"], np.float64)
    sign = np.sign(np.sum(v * ref["V"], axis=0))
    sign[sign == 0] = 1.0
    d2 = _u_dist2(state["A"].larray_padded, out["U"], jnp.asarray(ref["V"], jnp.float32),
                  jnp.asarray(1.0 / ref["S"], jnp.float32), jnp.asarray(sign, jnp.float32), state["nb"])
    return {
        "sv_rel": float(np.max(np.abs(s - ref["S"]) / ref["S"])),
        "relerr_gap": abs(float(out["rel_err"]) - ref["rel_err"]),
        "u_dist": float(np.sqrt(np.max(np.asarray(d2, np.float64)))),
        "v_dist": float(np.max(np.linalg.norm(v * sign[None, :] - ref["V"], axis=0))),
    }


# ------------------------------------------------------------------ control
@partial(jax.jit, static_argnames=("nb", "k"))
def _project_low(a, v, inv_s, nb: int, k: int):
    bs = a.shape[0] // nb
    vb = v.astype(jnp.bfloat16)

    def body(i, u):
        ab = jax.lax.dynamic_slice_in_dim(a, i * bs, bs, 0).astype(jnp.bfloat16)
        ub = jnp.matmul(ab, vb, preferred_element_type=jnp.float32) * inv_s[None, :]
        return jax.lax.dynamic_update_slice(u, ub, (i * bs, 0))

    return jax.lax.fori_loop(0, nb, body, jnp.zeros((a.shape[0], k), jnp.float32))


def control(state: dict) -> dict:
    """The reference's mathematics put in the program's place, every matmul
    in one bfloat16 pass: what `correct` has to refuse."""
    a = state["A"].larray_padded
    k = state["rank"]
    g = jnp.sum(_gram_blocks(a, state["ref_nb"], True), axis=0)
    lam, v = jnp.linalg.eigh(g)
    lam, v = jnp.maximum(lam[::-1], 0.0), v[:, ::-1]
    s = jnp.sqrt(lam[:k])
    rel_err = jnp.sqrt(jnp.maximum(jnp.sum(lam) - jnp.sum(lam[:k]), 0.0) / jnp.sum(lam))
    u = _project_low(a, v[:, :k], 1.0 / s, state["nb"], k)
    return {"U": u, "S": s, "V": v[:, :k], "rel_err": rel_err}


# ------------------------------------------------------------------- faults
def faults() -> dict:
    """Faults planted under the timed path, {name: (module, attribute,
    maker)}: ``maker(original)`` takes the attribute's place.  Read at the
    cell's own size by ``chipbench.control --faults`` and refused at rehearsal
    size by ``chipbench.selftest``.  One chip and no state kept from solve to
    solve, so a left-out exchange and an unchanged state are not faults this
    cell can have."""
    from heat_tpu.core.linalg import svdtools

    def altered(original):  # an answer altered where it is produced: one singular value, by 5e-5
        def f(*a, **kw):
            u, s, v, err = original(*a, **kw)
            return u, s.at[3].multiply(1.00005), v, err
        return f

    def half(original):  # the second half of the rows left out, the factors taken from the rest
        def f(dense, *a, **kw):
            m = dense.shape[0]
            u, s, v, err = original(dense[: m // 2], *a, **kw)
            return jnp.pad(u, ((0, m - m // 2), (0, 0))), s, v, err
        return f

    return {"altered": (svdtools, "_hsvd_rank_jit", altered), "half": (svdtools, "_hsvd_rank_jit", half)}
