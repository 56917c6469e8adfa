"""Jit'd public wrappers for the Pallas kernels.

They compile for the TPU.  ``interpret=True`` executes the kernel bodies
in Python instead, for correctness checks against
:mod:`repro.kernels.ref` on a host without a chip; nothing picks it for
the caller.
"""
from __future__ import annotations

import functools

import jax

from .flash_attention import flash_attention_pallas
from .mlstm import mlstm_scan_pallas
from .ssd import ssd_scan_pallas


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "block_q", "block_k", "interpret")
)
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    block_q=128, block_k=128, interpret=False):
    """Flash attention.  q (B,H,Sq,D); k/v (B,KV,Sk,D) -> (B,H,Sq,D)."""
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bmat, Cmat, *, chunk=128, interpret=False):
    """Mamba2 SSD.  x (B,S,H,P), dt (B,S,H), A (H,), B/C (B,S,N)."""
    return ssd_scan_pallas(x, dt, A, Bmat, Cmat, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_scan(q, k, v, i_gate, f_gate, *, chunk=128, interpret=False):
    """Chunked mLSTM.  q/k/v (B,S,H,D), gates (B,S,H)."""
    return mlstm_scan_pallas(q, k, v, i_gate, f_gate, chunk=chunk, interpret=interpret)
