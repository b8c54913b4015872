"""numpy.linalg parity extensions beyond the reference's linalg set.

The reference implements det/inv/qr/svd/solve_triangular and leaves the
rest of numpy.linalg uncovered; these close the block.  Everything runs
on the dense global view (GSPMD distributes the batched/matmul parts);
`eig`/`eigvals` have no TPU kernel in XLA and run on the in-process CPU
backend.
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..dndarray import DNDarray

__all__ = [
    "cholesky",
    "cond",
    "eig",
    "eigh",
    "eigvals",
    "eigvalsh",
    "lstsq",
    "matrix_power",
    "matrix_rank",
    "multi_dot",
    "pinv",
    "slogdet",
    "solve",
    "tensorinv",
    "tensorsolve",
]


def _d(x):
    if isinstance(x, DNDarray):
        d = x._dense()
        if not jnp.issubdtype(d.dtype, jnp.inexact):
            d = d.astype(jnp.float32)
        return d
    return jnp.asarray(x)


def _ref(*xs):
    for x in xs:
        if isinstance(x, DNDarray):
            return x
    return None


def _wrap(result, *operands):
    from ..napi import _auto_split

    ref = _ref(*operands)
    if ref is None:
        return DNDarray.from_dense(result, None, None, None)
    return DNDarray.from_dense(result, _auto_split(result, ref), ref.device, ref.comm)


def _on_cpu(fn, *arrays):
    """Run fn on the in-process CPU backend (for factorizations without a
    TPU kernel: nonsymmetric eig)."""
    cpu = jax.devices("cpu")[0]
    moved = [jax.device_put(a, cpu) for a in arrays]
    return fn(*moved)


def cholesky(a):
    """Lower-triangular Cholesky factor of an SPD matrix.

    2-D split matrices run the distributed blocked right-looking program
    (factorizations.py): the matrix stays row-sharded, per-device memory
    O(n*b) — a split matrix larger than one device's memory factorizes."""
    from .factorizations import cholesky_dist, supports_dist_factor

    if isinstance(a, DNDarray) and supports_dist_factor(a):
        return cholesky_dist(a)
    return _wrap(jnp.linalg.cholesky(_d(a)), a)


def cond(x, p=None):
    """Condition number with respect to norm ``p``.

    .. note:: Beyond the reference's surface; computed as a global
       ``jnp.linalg`` call on the dense view — a SPLIT operand larger
       than one device's memory gathers here (no distributed
       eigensolver yet; see docs/design.md).
    """
    return _wrap(jnp.linalg.cond(_d(x), p=p), x)


def eigh(a, UPLO: str = "L"):
    """Eigendecomposition of a symmetric/Hermitian matrix.

    .. note:: Beyond the reference's surface; computed as a global
       ``jnp.linalg`` call on the dense view — a SPLIT operand larger
       than one device's memory gathers here (no distributed
       eigensolver yet; see docs/design.md).
    """
    w, v = jnp.linalg.eigh(_d(a), UPLO=UPLO)
    return _wrap(w, a), _wrap(v, a)


def eigvalsh(a, UPLO: str = "L"):
    """Eigenvalues of a symmetric/Hermitian matrix (gathers a split
    operand to the dense view — see the note on :func:`eigh`)."""
    return _wrap(jnp.linalg.eigvalsh(_d(a), UPLO=UPLO), a)


def eig(a):
    """General eigendecomposition (no TPU kernel in XLA: runs on the
    in-process CPU backend; complex output)."""
    w, v = _on_cpu(jnp.linalg.eig, _d(a))
    return _wrap(w, a), _wrap(v, a)


def eigvals(a):
    return _wrap(_on_cpu(jnp.linalg.eigvals, _d(a)), a)


def _qr_full_rank(r_small) -> bool:
    """Numerical full-rank check on R's diagonal (one tiny host fetch);
    the TS-QR normal route is only valid at full rank — rank-deficient
    systems fall back to the SVD-based paths."""
    rd = np.abs(np.asarray(jnp.diagonal(r_small)))
    n = rd.shape[0]
    eps = float(jnp.finfo(r_small.dtype).eps)
    return bool(rd.min() > rd.max() * eps * max(n, 1) * 16)


def _tall_split0(a) -> bool:
    """Tall row-split matrix on a mesh: the TS-QR normal route applies
    (each device block has at least as many rows as columns)."""
    return (
        isinstance(a, DNDarray)
        and a.ndim == 2
        and a.split == 0
        and a.comm.size > 1
        and a.shape[0] // a.comm.size >= a.shape[1]
    )


def lstsq(a, b, rcond=None):
    """Least-squares solve; returns (x, residuals, rank, singular values).

    Tall row-split systems route through the distributed TS-QR
    (qr.py shard_map tree merge): x = R^-1 Q^T b, with only the small
    (n, n) R replicated — the reference capability without a gather.
    ``rank`` is a lazy 0-d array — no host sync is forced inside the call;
    use ``int(rank)`` to materialize it."""
    ref = _ref(a, b)
    if rcond is None and _tall_split0(a) and isinstance(b, DNDarray):
        from . import basics
        from .qr import qr as ht_qr

        q, rm = ht_qr(a)
        r_small = rm._dense()
        if _qr_full_rank(r_small):
            qtb = basics.matmul(
                basics.transpose(q), b.reshape((b.shape[0], 1)) if b.ndim == 1 else b
            )
            x = jax.scipy.linalg.solve_triangular(r_small, qtb._dense(), lower=False)
            if b.ndim == 1:
                x = x[:, 0]
            # numpy contract: residual sum of squares and the TRUE spectrum
            # (singular values of A == singular values of R)
            r_vec = _d(b) - jnp.matmul(_d(a), x)
            rss = jnp.sum(r_vec * r_vec, axis=0)
            resid = rss.reshape((1,)) if b.ndim == 1 else rss
            rank = jnp.asarray(a.shape[1])
            sv = jnp.linalg.svd(r_small, compute_uv=False)
            return (_wrap(x, ref), _wrap(resid, ref), _wrap(rank, ref), _wrap(sv, ref))
    x, resid, rank, sv = jnp.linalg.lstsq(_d(a), _d(b), rcond=rcond)
    return (_wrap(x, ref), _wrap(resid, ref), _wrap(rank, ref), _wrap(sv, ref))


def matrix_power(a, n: int):
    """Repeated matrix product (gathers a split operand to the dense
    view — see the note on :func:`eigh`)."""
    return _wrap(jnp.linalg.matrix_power(_d(a), n), a)


def matrix_rank(a, tol=None):
    """Matrix rank as a lazy 0-d array (no forced host sync; ``int()`` it
    to materialize).  Gathers a split operand to the dense view for the
    SVD — see the note on :func:`eigh`."""
    return _wrap(jnp.linalg.matrix_rank(_d(a), rtol=None if tol is None else tol), a)


def multi_dot(arrays):
    """Chained matmul with optimal association order."""
    dense = [_d(a) for a in arrays]
    return _wrap(jnp.linalg.multi_dot(dense), *list(arrays))


def pinv(a, rcond=None, hermitian: bool = False):
    """Moore-Penrose pseudo-inverse.

    Tall full-rank row-split matrices: A+ = R^-1 Q^T over the distributed
    TS-QR (only the small R is replicated; Q stays row-sharded)."""
    if rcond is None and not hermitian and _tall_split0(a):
        from . import basics
        from .qr import qr as ht_qr

        q, rm = ht_qr(a)
        r_small = rm._dense()
        if _qr_full_rank(r_small):
            rinv = jnp.linalg.inv(r_small)  # (n, n), replicated
            rinv_arr = DNDarray.from_dense(rinv, None, a.device, a.comm)
            return basics.matmul(rinv_arr, basics.transpose(q))
    return _wrap(jnp.linalg.pinv(_d(a), rtol=rcond, hermitian=hermitian), a)


def slogdet(a):
    """Sign and log|det|."""
    sign, logabs = jnp.linalg.slogdet(_d(a))
    return _wrap(sign, a), _wrap(logabs, a)


def solve(a, b):
    """Solve the linear system a x = b.

    A 2-D split square ``a`` takes the distributed LU + blocked
    substitution path; everything else (batched, replicated) uses XLA."""
    from .factorizations import solve_dist, supports_dist_factor

    if (
        isinstance(a, DNDarray)
        and supports_dist_factor(a)
        and isinstance(b, DNDarray)
        and b.ndim in (1, 2)
    ):
        return solve_dist(a, b)
    return _wrap(jnp.linalg.solve(_d(a), _d(b)), _ref(a, b))


def tensorinv(a, ind: int = 2):
    return _wrap(jnp.linalg.tensorinv(_d(a), ind=ind), a)


def tensorsolve(a, b, axes=None):
    return _wrap(jnp.linalg.tensorsolve(_d(a), _d(b), axes=axes), _ref(a, b))
