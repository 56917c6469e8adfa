"""Chunked mLSTM Pallas kernel (TPU target, xLSTM arXiv:2405.04517).

Grid (B, H, n_chunks), chunk innermost; the matrix memory S (D, D), the
normalizer n (1, D) and the stabilizer m (scalar) persist in VMEM scratch
across the sequential chunk dimension.  All gating math is fp32.

Layouts (pre-transposed by ops.py):
  q/k/v (B, H, nc, Q, D)   ig/fg (B, H, 1, S)   ->  h (B, H, nc, Q, D)

On the chip, Q must be a multiple of 128 (or all of S): the gate blocks
are (1, Q) rows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import chunk_masks, col_to_row, cumsum_col, row_to_col

NEG = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, ig_ref, fg_ref, h_ref,
                  s_ref, n_ref, m_ref, *, chunk: int, head_dim: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)

    Q, D = chunk, head_dim
    causal, eye = chunk_masks(Q)
    q = q_ref[0, 0, 0].astype(jnp.float32) / math.sqrt(D)   # (Q, D)
    k = k_ref[0, 0, 0].astype(jnp.float32)
    v = v_ref[0, 0, 0].astype(jnp.float32)
    ig = ig_ref[0, 0].astype(jnp.float32)                   # (1, Q)
    logf = jax.nn.log_sigmoid(fg_ref[0, 0].astype(jnp.float32))

    b_col = cumsum_col(logf, causal)                        # (Q, 1)
    b = col_to_row(b_col, eye)                              # (1, Q)
    ig_col = row_to_col(ig, eye)                            # (Q, 1)
    total = b[:, Q - 1:]                                    # (1, 1)
    m_p = m_ref[...]                                        # (1, 1)

    # intra log-weights: l_ij = b_i - b_j + ig_j  (j <= i)
    diff = jnp.where(causal, b_col - b + ig, NEG)           # (Q, Q)
    m_intra = jnp.max(diff, axis=1, keepdims=True)          # (Q, 1)

    # per-position stabilizer
    m_i = jnp.maximum(m_p + b_col, m_intra)                 # (Q, 1)
    inter_scale = jnp.where(m_p <= NEG, 0.0, jnp.exp(m_p + b_col - m_i))

    num = jax.lax.dot_general(
        q, s_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * inter_scale
    den = jnp.sum(q * n_ref[...], axis=1, keepdims=True) * inter_scale

    qk = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                       # (Q, Q)
    wts = jnp.where(causal, jnp.exp(diff - m_i), 0.0)
    num += jax.lax.dot_general(
        qk * wts, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    den += jnp.sum(qk * wts, axis=1, keepdims=True)

    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_i))
    h_ref[0, 0, 0] = h.astype(h_ref.dtype)

    # state update (stabilized)
    w = total - b_col + ig_col                              # (Q, 1)
    m_chunk = jnp.max(w, axis=0, keepdims=True)             # (1, 1)
    m_new = jnp.maximum(m_p + total, m_chunk)
    scale_old = jnp.where(m_p <= NEG, 0.0, jnp.exp(m_p + total - m_new))
    kw = k * jnp.exp(w - m_new)                             # (Q, D)
    s_ref[...] = s_ref[...] * scale_old + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    n_ref[...] = n_ref[...] * scale_old + jnp.sum(kw, axis=0, keepdims=True)
    m_ref[...] = m_new


def mlstm_scan_pallas(
    q: jax.Array,        # (B, S, H, D)
    k: jax.Array,
    v: jax.Array,
    i_gate: jax.Array,   # (B, S, H)
    f_gate: jax.Array,   # (B, S, H)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, S, H, D = q.shape
    assert S % chunk == 0
    nc = S // chunk
    Q = chunk

    def tr(a):
        return jnp.moveaxis(a, 2, 1).reshape(B, H, nc, Q, *a.shape[3:])

    qt, kt, vt = tr(q), tr(k), tr(v)
    # Gates as lane-major rows: a (1, Q) block of (B, H, 1, S) meets the
    # (8, 128) tiling rule where a (Q,) block of (B, H, nc, Q) does not.
    igt = jnp.moveaxis(i_gate, 2, 1).reshape(B, H, 1, S)
    fgt = jnp.moveaxis(f_gate, 2, 1).reshape(B, H, 1, S)

    kernel = functools.partial(_mlstm_kernel, chunk=Q, head_dim=D)
    h = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, D), lambda b, h_, c: (b, h_, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, D), lambda b, h_, c: (b, h_, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, D), lambda b, h_, c: (b, h_, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h_, c: (b, h_, 0, c)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h_, c: (b, h_, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, Q, D), lambda b, h_, c: (b, h_, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nc, Q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((D, D), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, igt, fgt)
    return jnp.moveaxis(h.reshape(B, H, S, D), 1, 2)
