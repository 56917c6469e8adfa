"""Mamba2 SSD chunked-scan Pallas kernel (TPU target).

Grid (B, H, n_chunks): the chunk dimension is innermost and TPU grids are
sequential, so the (N, P) recurrent state lives in VMEM scratch across
chunk steps — the HBM<->VMEM traffic per chunk is exactly one (Q, P) x
tile, one (Q, N) B/C tile pair and the (Q, P) output tile, which is what
makes the chunked formulation memory-optimal on TPU.

Layouts (pre-transposed by ops.py):
  x  (B, H, nc, Q, P)   dt, dt*A (B, H, 1, S)
  Bm (B, nc, Q, N)      Cm (B, nc, Q, N)
  -> y (B, H, nc, Q, P)

On the chip, Q must be a multiple of 128 (or all of S): the dt blocks
are (1, Q) rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import chunk_masks, col_to_row, cumsum_col, row_to_col


def _ssd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    Q = chunk
    causal, eye = chunk_masks(Q)
    x = x_ref[0, 0, 0].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)         # (1, Q)
    dA = da_ref[0, 0]                             # (1, Q) = dt * A_h, negative
    Bm = b_ref[0, 0].astype(jnp.float32)          # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)          # (Q, N)

    cum_col = cumsum_col(dA, causal)              # (Q, 1)
    cum = col_to_row(cum_col, eye)                # (1, Q)
    total = cum[:, Q - 1:]                        # (1, 1)

    # intra-chunk: w_ij = (C_i . B_j) exp(cum_i - cum_j) dt_j  (j <= i)
    cb = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                             # (Q, Q)
    w = jnp.where(causal, cb * jnp.exp(cum_col - cum) * dt, 0.0)
    y = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    # inter-chunk: C_i . S_prev, decayed into the chunk
    y += jax.lax.dot_general(
        Cm, state_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * jnp.exp(cum_col)

    # state update: S = exp(total) S + sum_j exp(total - cum_j) dt_j B_j x_j^T
    rem = jnp.exp(total - cum_col) * row_to_col(dt, eye)   # (Q, 1)
    state_ref[...] = state_ref[...] * jnp.exp(total) + jax.lax.dot_general(
        Bm * rem, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)


def ssd_scan_pallas(
    x: jax.Array,        # (B, S, H, P)
    dt: jax.Array,       # (B, S, H)  (positive, post-softplus)
    A: jax.Array,        # (H,)       (negative)
    Bmat: jax.Array,     # (B, S, N)
    Cmat: jax.Array,     # (B, S, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, S, H, P = x.shape
    N = Bmat.shape[-1]
    assert S % chunk == 0
    nc = S // chunk
    Q = chunk

    xt = jnp.moveaxis(x, 2, 1).reshape(B, H, nc, Q, P)
    # Per-position vectors as lane-major rows: a (1, Q) block of
    # (B, H, 1, S) meets the (8, 128) tiling rule where a (Q,) block of
    # (B, H, nc, Q) does not.  A is folded in here, so no (1,)-block of
    # the (H,) vector reaches the kernel.
    dtt = jnp.moveaxis(dt, 2, 1).reshape(B, H, 1, S)
    dAt = dtt.astype(jnp.float32) * A.astype(jnp.float32)[None, :, None, None]
    Bq = Bmat.reshape(B, nc, Q, N)
    Cq = Cmat.reshape(B, nc, Q, N)

    kernel = functools.partial(_ssd_kernel, chunk=Q)
    y = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nc, Q, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, dAt, Bq, Cq)
    return jnp.moveaxis(y.reshape(B, H, S, P), 1, 2)
