"""What every driver's data generator needs: a key from ``--seed`` and a
split of the rows into equal blocks."""

from __future__ import annotations

import jax


def key(seed: int):
    """``--seed`` may need more than 32 signed bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def blocks(rows: int, want: int) -> int:
    """The largest number of equal row blocks that is at most ``want``."""
    return next(nb for nb in range(min(want, rows), 0, -1) if rows % nb == 0)
