"""Plain float32 decoder-only transformer, as configured.

Pre-norm blocks with RMSNorm: causal multi-head attention with rotary
position embedding on every head dimension (the two halves of a head
rotated by ``theta ** (-i / half)`` per position), then a SiLU-gated
feed-forward.  A final RMSNorm and an untied output head.  Attention is
computed a block of queries at a time, over all keys, with a full
softmax; that bounds memory and changes no arithmetic.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import fan_in_scale, head_loss, rmsnorm

QUERY_BLOCK = 512


def param_table(m: dict) -> dict:
    d, H, KV, V, L, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                          m["vocab"], m["n_layers"], m["d_ff"])
    hd = d // H
    t = {
        "embed/table": ((V, d), "normal", 0.02),
        "final_norm/scale": ((d,), "ones", 1.0),
        "head/w": ((d, V), "normal", fan_in_scale(d)),
    }
    blocks = {
        "ln_attn/scale": ((d,), "ones", 1.0),
        "ln_mlp/scale": ((d,), "ones", 1.0),
        "attn/wq": ((d, H, hd), "normal", fan_in_scale(d)),
        "attn/wk": ((d, KV, hd), "normal", fan_in_scale(d)),
        "attn/wv": ((d, KV, hd), "normal", fan_in_scale(d)),
        "attn/wo": ((H, hd, d), "normal", fan_in_scale(H * hd)),
        "mlp/wi_gate": ((d, ff), "normal", fan_in_scale(d)),
        "mlp/wi_up": ((d, ff), "normal", fan_in_scale(d)),
        "mlp/wo": ((ff, d), "normal", fan_in_scale(ff)),
    }
    for k, (shape, kind, scale) in blocks.items():
        t[f"blocks/{k}"] = ((L,) + shape, kind, scale)
    return t


def _rotate(x, theta):
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs        # (S, half)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(p, x, m, mm):
    B, S, d = x.shape
    H, KV = m["n_heads"], m["n_kv_heads"]
    hd = d // H
    q = _rotate(mm("bsd,dhk->bshk", x, p["attn/wq"]), m["rope_theta"])
    k = _rotate(mm("bsd,dhk->bshk", x, p["attn/wk"]), m["rope_theta"])
    v = mm("bsd,dhk->bshk", x, p["attn/wv"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    block = min(QUERY_BLOCK, S) if S % min(QUERY_BLOCK, S) == 0 else S
    qs = jnp.moveaxis(q.reshape(B, S // block, block, H, hd), 1, 0)
    starts = jnp.arange(0, S, block)

    @jax.checkpoint
    def one(args):
        qb, start = args
        scores = mm("bqhk,bshk->bhqs", qb, k) / math.sqrt(hd)
        q_pos = start + jnp.arange(block)
        causal = jnp.arange(S)[None, :] <= q_pos[:, None]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        return mm("bhqs,bshk->bqhk", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(one, (qs, starts))
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, H, hd)
    return mm("bshk,hkd->bsd", out, p["attn/wo"])


def _mlp(p, x, mm):
    gate = mm("bsd,df->bsf", x, p["mlp/wi_gate"])
    up = mm("bsd,df->bsf", x, p["mlp/wi_up"])
    return mm("bsf,fd->bsd", jax.nn.silu(gate) * up, p["mlp/wo"])


def loss_sum(params, tokens, labels, m, mm):
    eps = m["norm_eps"]
    stacked = {k[len("blocks/"):]: v for k, v in params.items() if k.startswith("blocks/")}

    @jax.checkpoint
    def layer(x, p):
        x = x + _attention(p, rmsnorm(x, p["ln_attn/scale"], eps), m, mm)
        x = x + _mlp(p, rmsnorm(x, p["ln_mlp/scale"], eps), mm)
        return x, None

    x = params["embed/table"][tokens]
    x, _ = jax.lax.scan(layer, x, stacked)
    y = rmsnorm(x, params["final_norm/scale"], eps)
    return head_loss(y, params["head/w"], labels, mm)
