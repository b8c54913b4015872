"""Halo (ghost-cell) exchange, the TPU-native analog of
``DNDarray.get_halo`` (dndarray.py:387-464).

The reference pairs Isend/Irecv with the previous/next rank along the
split axis and concatenates the received rows.  Here the same pattern is a
``jax.shard_map`` body using two ``lax.ppermute`` ring shifts over ICI —
the canonical stencil-parallel primitive (SURVEY.md §5 notes this is
exactly what ring-attention/context-parallel kernels need).
"""

from __future__ import annotations

import functools

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from .comm import Communication

__all__ = ["halo_exchange", "with_halos"]


def halo_exchange(comm: Communication, local: jnp.ndarray, halo_size: int, axis: int = 0):
    """Inside-shard_map body: return (halo_prev, halo_next) for this shard.

    ``halo_prev`` holds the last ``halo_size`` rows of the previous rank,
    ``halo_next`` the first ``halo_size`` rows of the next rank (edge ranks
    receive zeros, matching the reference's None-halo at the ends).
    """
    n = comm.size
    name = comm.axis_name
    # send my first rows to the previous rank -> they arrive as halo_next
    first = jax.lax.slice_in_dim(local, 0, halo_size, axis=axis)
    last = jax.lax.slice_in_dim(local, local.shape[axis] - halo_size, local.shape[axis], axis=axis)
    halo_next = jax.lax.ppermute(first, name, [(i, (i - 1) % n) for i in range(n)])
    halo_prev = jax.lax.ppermute(last, name, [(i, (i + 1) % n) for i in range(n)])
    idx = jax.lax.axis_index(name)
    halo_prev = jnp.where(idx == 0, jnp.zeros_like(halo_prev), halo_prev)
    halo_next = jnp.where(idx == n - 1, jnp.zeros_like(halo_next), halo_next)
    return halo_prev, halo_next


def with_halos(comm: Communication, padded: jnp.ndarray, halo_size: int, split: int):
    """Map a padded global array to per-shard [halo_prev | local | halo_next]
    blocks, returned as one sharded array with an extra leading shard axis.

    This is the collective the reference's ``array_with_halos``
    (dndarray.py:360) plus ``__cat_halo`` (:465) perform with paired
    send/recvs.
    """
    if split != 0:
        padded = jnp.moveaxis(padded, split, 0)

    out = _with_halos_fn(comm, halo_size)(padded)  # (n_shards, chunk + 2*halo, ...)
    if split != 0:
        out = jnp.moveaxis(out, 1, split + 1)
    return out


@functools.lru_cache(maxsize=64)
def _with_halos_fn(comm: Communication, halo_size: int):
    """Jitted, cached halo-concat executable (rebuilding the shard_map per
    call would retrace/recompile each time)."""

    def body(local):
        prev, nxt = halo_exchange(comm, local, halo_size, axis=0)
        return jnp.concatenate([prev, local, nxt], axis=0)[None]

    return jax.jit(
        _shard_map(
            body,
            mesh=comm.mesh,
            in_specs=P(comm.axis_name),
            out_specs=P(comm.axis_name),
        )
    )
