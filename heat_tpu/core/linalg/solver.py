"""Iterative/triangular solvers, analog of heat/core/linalg/solver.py.

``cg`` (solver.py:16-66) and ``lanczos`` (:69-274) are compositions of the
distributed ops API and port structurally; ``solve_triangular`` (:275-463)
— blocked backward substitution with Bcasts in the reference — lowers to
XLA's triangular solve over the sharded operand.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import types
from ..dndarray import DNDarray
from ..sanitation import sanitize_in
from .basics import matmul, transpose

__all__ = ["cg", "lanczos", "solve_triangular"]


from functools import partial


@partial(jax.jit, static_argnames=("max_iter",))
def _cg_loop(Ad: jax.Array, bd: jax.Array, x0d: jax.Array, max_iter: int) -> jax.Array:
    """Conjugate-gradient iteration compiled as one program (tol 1e-10 on
    the residual norm, matching the reference's stop test solver.py:46)."""
    hp = jax.lax.Precision.HIGHEST

    r0 = bd - jnp.matmul(Ad, x0d, precision=hp)
    init = (x0d, r0, r0, jnp.vdot(r0, r0), jnp.int32(0))

    def cond(carry):
        x, r, p, rs, it = carry
        return jnp.logical_and(it < max_iter, jnp.sqrt(rs) >= 1e-10)

    def body(carry):
        x, r, p, rs, it = carry
        Ap = jnp.matmul(Ad, p, precision=hp)
        alpha = rs / jnp.vdot(p, Ap)
        x = x + alpha * p
        r = r - alpha * Ap
        rsnew = jnp.vdot(r, r)
        p = r + (rsnew / rs) * p
        return x, r, p, rsnew, it + 1

    x, _, _, _, _ = jax.lax.while_loop(cond, body, init)
    return x


def cg(A: DNDarray, b: DNDarray, x0: DNDarray, out: Optional[DNDarray] = None) -> DNDarray:
    """Conjugate gradients for SPD systems (solver.py:16)."""
    if not isinstance(A, DNDarray) or not isinstance(b, DNDarray) or not isinstance(x0, DNDarray):
        raise TypeError(f"A, b and x0 need to be DNDarrays, but were {type(A)}, {type(b)}, {type(x0)}")
    if A.ndim != 2:
        raise RuntimeError("A needs to be a 2D matrix")
    if b.ndim != 1:
        raise RuntimeError("b needs to be a 1D vector")
    if x0.ndim != 1:
        raise RuntimeError("c needs to be a 1D vector")

    # whole Krylov iteration as one on-device while_loop: a Python loop
    # with a float() residual check costs one device->host round trip per
    # step
    Ad = A._dense()
    if not types.heat_type_is_inexact(A.dtype):
        Ad = Ad.astype(jnp.float32)
    bd = b._dense().astype(Ad.dtype)
    x0d = x0._dense().astype(Ad.dtype)
    xd = _cg_loop(Ad, bd, x0d, len(b))
    result = DNDarray.from_dense(xd, b.split, b.device, b.comm)
    if out is not None:
        out._replace(result.larray_padded)
        return out
    return result


def lanczos(
    A: DNDarray,
    m: int,
    v0: Optional[DNDarray] = None,
    V_out: Optional[DNDarray] = None,
    T_out: Optional[DNDarray] = None,
) -> Tuple[DNDarray, DNDarray]:
    """Lanczos tridiagonalization of a symmetric/Hermitian matrix
    (solver.py:69): m Krylov steps with full reorthogonalization.
    """
    if not isinstance(A, DNDarray):
        raise TypeError(f"A needs to be a DNDarray, but was {type(A)}")
    if not isinstance(m, int) or m <= 0:
        raise TypeError(f"m must be a positive integer, got {m}")
    if A.ndim != 2 or A.shape[0] != A.shape[1]:
        raise RuntimeError("A needs to be a square matrix")

    n = A.shape[0]
    dense_A = A._dense()
    dtype = dense_A.dtype
    is_complex = types.heat_type_is_complexfloating(A.dtype)

    from .. import random as ht_random

    if v0 is None:
        v = ht_random.randn(n, dtype=types.canonical_heat_type(jnp.float32), comm=A.comm)._dense().astype(dtype)
        v = v / jnp.linalg.norm(v)
    else:
        v = v0._dense().astype(dtype)

    V, T = _lanczos_impl(dense_A, v, m, is_complex)

    V_res = DNDarray.from_dense(V, A.split, A.device, A.comm)
    T_res = DNDarray.from_dense(T, None, A.device, A.comm)
    if V_out is not None:
        V_out._replace(V_res.larray_padded)
        V_res = V_out
    if T_out is not None:
        T_out._replace(T_res.larray_padded)
        T_res = T_out
    return V_res, T_res


from functools import partial as _partial


@_partial(jax.jit, static_argnames=("m", "is_complex"))
def _lanczos_impl(dense_A: jax.Array, v: jax.Array, m: int, is_complex: bool):
    """Krylov loop with static shapes, compiled once.

    Reorthogonalization projects against the FULL (n, m) basis every step:
    the not-yet-filled columns are zero, so ``V (V^H w)`` is identical to
    the reference's growing ``V[:, :j+1]`` product (solver.py:153+) while
    keeping every iteration the same shape — one compilation instead of m.
    """
    n = dense_A.shape[0]
    dtype = dense_A.dtype
    hi = jax.lax.Precision.HIGHEST

    V0 = jnp.zeros((n, m), dtype=dtype).at[:, 0].set(v)
    T0 = jnp.zeros((m, m), dtype=jnp.float32)

    def alpha_of(vj, w):
        a = jnp.vdot(vj, w)
        return jnp.real(a) if is_complex else a

    def body(j, carry):
        V, T, beta, v_prev = carry
        vj = jax.lax.dynamic_slice_in_dim(V, j, 1, axis=1)[:, 0]
        w = jnp.matmul(dense_A, vj, precision=hi)
        alpha = alpha_of(vj, w)
        w = w - alpha * vj - beta * v_prev
        w = w - jnp.matmul(V, jnp.matmul(jnp.conj(V).T, w, precision=hi), precision=hi)
        T = T.at[j, j].set(alpha.astype(jnp.float32))
        beta_new = jnp.linalg.norm(w)
        T = T.at[j, j + 1].set(beta_new.astype(jnp.float32))
        T = T.at[j + 1, j].set(beta_new.astype(jnp.float32))
        v_next = jnp.where(beta_new > 1e-10, w / jnp.maximum(beta_new, 1e-30).astype(dtype), w)
        V = V.at[:, j + 1].set(v_next)
        return V, T, beta_new.astype(dtype if not is_complex else jnp.float32), vj

    beta0 = jnp.zeros((), jnp.float32 if is_complex else dtype)
    V, T, beta, v_prev = jax.lax.fori_loop(
        0, m - 1, body, (V0, T0, beta0, jnp.zeros_like(v))
    )
    # final step: diagonal entry only (no j+1 column to fill)
    vj = V[:, m - 1]
    w = jnp.matmul(dense_A, vj, precision=hi)
    T = T.at[m - 1, m - 1].set(alpha_of(vj, w).astype(jnp.float32))
    return V, T


def solve_triangular(A: DNDarray, b: DNDarray) -> DNDarray:
    """Solve A x = b for upper-triangular A (solver.py:275)."""
    sanitize_in(A)
    sanitize_in(b)
    if A.ndim < 2 or A.shape[-1] != A.shape[-2]:
        raise ValueError("A must be a (batch of) square upper triangular matrix")
    import jax.scipy.linalg as jsl

    a_dense = A._dense()
    b_dense = b._dense()
    if not types.heat_type_is_inexact(A.dtype):
        a_dense = a_dense.astype(jnp.float32)
        b_dense = b_dense.astype(jnp.float32)
    result = jsl.solve_triangular(a_dense, b_dense, lower=False)
    return DNDarray.from_dense(result, b.split, b.device, b.comm)
