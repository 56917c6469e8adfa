"""Per-position vectors inside a chunk kernel, in layouts Mosaic accepts.

A chunk's gate or step-size vector arrives as a lane-major ``(1, Q)``
row: a 1-D ``(Q,)`` block breaks the TPU's (8, 128) tiling rule, and a
row block of a ``(B, H, 1, S)`` array satisfies it.  The scan math also
needs the same values down the sublanes, as a ``(Q, 1)`` column.  These
helpers move between the two with masked reductions, which are exact in
f32 (every other term is a zero), so no transpose or low-precision
matmul touches the recurrence.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def chunk_masks(q: int) -> tuple[jax.Array, jax.Array]:
    """``(causal, eye)`` masks of shape (Q, Q): ``[i, j]`` is ``j <= i``
    and ``j == i`` respectively."""
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return col <= row, col == row


def row_to_col(row: jax.Array, eye: jax.Array) -> jax.Array:
    """(1, Q) row -> (Q, 1) column holding the same values."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def col_to_row(col: jax.Array, eye: jax.Array) -> jax.Array:
    """(Q, 1) column -> (1, Q) row holding the same values."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def cumsum_col(row: jax.Array, causal: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a (1, Q) row, as a (Q, 1) column."""
    return jnp.sum(jnp.where(causal, row, 0.0), axis=1, keepdims=True)
