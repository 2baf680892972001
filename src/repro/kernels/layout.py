"""Row/column moves inside a Pallas TPU kernel.

A per-position vector is either a ``(c, 1)`` column (what a lane reduction
of a ``(c, d)`` tile gives) or a ``(1, c)`` row (the layout a ``(1, 1, c)``
block of an ``(N, 1, S)`` array arrives in: the last two block dims must be
multiples of ``(8, 128)`` or the whole array dims, so ``(1, c)`` rows are
the compact way to move per-position values between HBM and VMEM). The
chip's compiler lowers neither ``jnp.cumsum`` nor a transpose of a single
column, so these helpers do both with a mask and one reduction: exact in
fp32, since every sum adds one nonzero term per output or is the sum
``jnp.cumsum`` would form.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def tri(c: int):
    """``(row, col)`` index grids of a ``(c, c)`` tile."""
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0),
            jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))


def col_to_row(x):
    """``(c, 1)`` column -> ``(1, c)`` row (a masked sublane sum)."""
    row, col = tri(x.shape[0])
    return jnp.sum(jnp.where(row == col, x, 0.0), axis=0, keepdims=True)


def row_to_col(x):
    """``(1, c)`` row -> ``(c, 1)`` column (a masked lane sum)."""
    row, col = tri(x.shape[1])
    return jnp.sum(jnp.where(row == col, x, 0.0), axis=1, keepdims=True)


def cumsum_col(x):
    """Inclusive cumulative sum of the ``(1, c)`` row ``x``, as a ``(c, 1)``
    column (a masked lane sum)."""
    row, col = tri(x.shape[1])
    return jnp.sum(jnp.where(col <= row, x, 0.0), axis=1, keepdims=True)


def suffix_sum_row(x):
    """Inclusive suffix sum of the ``(c, 1)`` column ``x``, as a ``(1, c)``
    row (a masked sublane sum)."""
    row, col = tri(x.shape[0])
    return jnp.sum(jnp.where(row >= col, x, 0.0), axis=0, keepdims=True)
