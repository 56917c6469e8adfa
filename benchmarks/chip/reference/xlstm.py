"""Plain float32 xLSTM language model (arXiv:2405.04517), as configured.

Blocks come in units of ``xlstm_slstm_every - 1`` mLSTM blocks and one
sLSTM block, each pre-normed with RMSNorm and added to the residual.

- mLSTM: an up-projection to ``2 * dp`` (``dp = proj_factor * d``) split
  into the cell input and an output gate ``z``; q, k, v and the per-head
  input and forget preactivations are projections of the cell input.
  The cell is the paper's parallel form: with F the cumulative
  log-sigmoid forget gate, log D[i, j] = F[i] - F[j] + i_gate[j] for
  j <= i, stabilised by its row maximum m; C = (q k^T / sqrt(D)) * D',
  h = C v / max(|sum_j C|, exp(-m)).  Then h * silu(z) * out_scale and a
  down-projection.
- sLSTM: input projection plus bias to four gates (z, i, f, o), a
  block-diagonal recurrent matrix per head and gate, exponential input
  and forget gates with the stabiliser state m, c and n states, then a
  GELU (tanh form) gated feed-forward of width int(4 d / 3).

The departures of the configuration from the paper (RMSNorm, no causal
convolution, no learnable skip) are listed in its ``assumed``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import fan_in_scale, head_loss, rmsnorm


def param_table(m: dict) -> dict:
    d, H, V = m["d_model"], m["n_heads"], m["vocab"]
    every = m["xlstm_slstm_every"]
    units = m["n_layers"] // every
    dp = int(m["xlstm_proj_factor"] * d)
    dh = d // H
    ff = int(4 * d / 3)
    t = {
        "embed/table": ((V, d), "normal", 0.02),
        "final_norm/scale": ((d,), "ones", 1.0),
        "head/w": ((d, V), "normal", fan_in_scale(d)),
    }

    def add(name, shape, kind="normal", scale=None):
        if scale is None and kind == "normal":
            scale = fan_in_scale(shape[0])
        t[f"blocks/{name}"] = ((units,) + shape, kind, scale or 1.0)

    for i in range(every - 1):
        add(f"ln_m{i}/scale", (d,), "ones")
        add(f"mlstm{i}/up", (d, 2 * dp))
        add(f"mlstm{i}/wq", (dp, dp))
        add(f"mlstm{i}/wk", (dp, dp))
        add(f"mlstm{i}/wv", (dp, dp))
        add(f"mlstm{i}/w_if", (dp, 2 * H))
        add(f"mlstm{i}/out_scale", (dp,), "ones")
        add(f"mlstm{i}/down", (dp, d))
    add("ln_s/scale", (d,), "ones")
    add("slstm/w_in", (d, 4 * d))
    add("slstm/r", (4, H, dh, dh), scale=fan_in_scale(dh))
    add("slstm/bias", (4 * d,), "zeros")
    add("slstm/ff_gate", (d, ff))
    add("slstm/ff_up", (d, ff))
    add("slstm/ff_down", (ff, d))
    return t


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _mlstm(p, name, x, m, mm):
    B, S, d = x.shape
    H = m["n_heads"]
    dp = int(m["xlstm_proj_factor"] * d)
    D = dp // H
    up = mm("bsd,dk->bsk", x, p[f"{name}/up"])
    xm, z = up[..., :dp], up[..., dp:]
    q = mm("bsk,kj->bsj", xm, p[f"{name}/wq"]).reshape(B, S, H, D)
    k = mm("bsk,kj->bsj", xm, p[f"{name}/wk"]).reshape(B, S, H, D)
    v = mm("bsk,kj->bsj", xm, p[f"{name}/wv"]).reshape(B, S, H, D)
    gates = mm("bsk,kj->bsj", xm, p[f"{name}/w_if"])
    i_gate, f_gate = gates[..., :H], gates[..., H:]
    F = jnp.cumsum(jax.nn.log_sigmoid(f_gate), axis=1)            # (B,S,H)
    log_d = F[:, :, None, :] - F[:, None, :, :] + i_gate[:, None, :, :]
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    log_d = jnp.where(causal, log_d, -jnp.inf)                    # (B,i,j,H)
    row_max = jnp.max(log_d, axis=2)                              # (B,S,H)
    c = mm("bihd,bjhd->bijh", q, k) / math.sqrt(D) * jnp.exp(log_d - row_max[:, :, None, :])
    norm = jnp.maximum(jnp.abs(jnp.sum(c, axis=2)), jnp.exp(-row_max))
    h = mm("bijh,bjhd->bihd", c, v) / norm[..., None]
    h = h.reshape(B, S, dp) * jax.nn.silu(z) * p[f"{name}/out_scale"]
    return mm("bsk,kd->bsd", h, p[f"{name}/down"])


def _slstm(p, x, m, mm):
    B, S, d = x.shape
    H = m["n_heads"]
    dh = d // H
    pre = (mm("bsd,dk->bsk", x, p["slstm/w_in"]) + p["slstm/bias"]).reshape(B, S, 4, H, dh)
    R = p["slstm/r"]

    def step(carry, pre_t):
        c, n, h, stab = carry
        rec = mm("bhj,ghjk->bghk", h, R)
        z = jnp.tanh(pre_t[:, 0] + rec[:, 0])
        i = pre_t[:, 1] + rec[:, 1]
        f = pre_t[:, 2] + rec[:, 2]
        o = jax.nn.sigmoid(pre_t[:, 3] + rec[:, 3])
        new_stab = jnp.maximum(f + stab, i)
        i_p = jnp.exp(i - new_stab)
        f_p = jnp.exp(f + stab - new_stab)
        c = f_p * c + i_p * z
        n = f_p * n + i_p
        h = o * c / jnp.maximum(n, 1e-6)
        return (c, n, h, new_stab), h

    zero = jnp.zeros((B, H, dh), jnp.float32)
    _, hs = jax.lax.scan(step, (zero, zero, zero, zero), jnp.moveaxis(pre, 1, 0))
    y = jnp.moveaxis(hs, 0, 1).reshape(B, S, d)
    hid = _gelu_tanh(mm("bsd,df->bsf", y, p["slstm/ff_gate"])) * mm(
        "bsd,df->bsf", y, p["slstm/ff_up"])
    return mm("bsf,fd->bsd", hid, p["slstm/ff_down"])


def loss_sum(params, tokens, labels, m, mm):
    eps = m["norm_eps"]
    every = m["xlstm_slstm_every"]
    stacked = {k[len("blocks/"):]: v for k, v in params.items() if k.startswith("blocks/")}

    @jax.checkpoint
    def unit(x, p):
        for i in range(every - 1):
            x = x + _mlstm(p, f"mlstm{i}", rmsnorm(x, p[f"ln_m{i}/scale"], eps), m, mm)
        x = x + _slstm(p, rmsnorm(x, p["ln_s/scale"], eps), m, mm)
        return x, None

    x = params["embed/table"][tokens]
    x, _ = jax.lax.scan(unit, x, stacked)
    y = rmsnorm(x, params["final_norm/scale"], eps)
    return head_loss(y, params["head/w"], labels, mm)

