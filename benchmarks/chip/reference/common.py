"""What every plain reference shares: weights from the seed, matmuls at a
stated precision, RMSNorm, AdamW and the three-step training readings.

Nothing here imports the program.  A model's reference module gives
``param_table(model)`` (name -> shape, init, scale) and
``loss_sum(params, tokens, labels, model, mm)`` (summed next-token cross
entropy and the number of labels counted, for a block of rows).

Precision ``f32`` computes every matmul in float32 at ``HIGHEST``.
Precision ``fp8`` is the control: each matmul operand is scaled per
tensor and rounded to float8 e4m3 on the way forward, each cotangent to
e5m2 on the way back, and the products are summed in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

HIGHEST = jax.lax.Precision.HIGHEST
HEAD_CHUNK = 512
ADAM = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip_norm": 1.0}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole seed, wider ones than 32 bits included."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def init_params(table: dict, key: jax.Array) -> dict:
    """Weights from ``param_table``: ``normal`` leaves are N(0, scale**2),
    ``ones`` and ``zeros`` are constant.  Leaf i draws from fold_in(key, i)
    in sorted name order."""
    out = {}
    for i, name in enumerate(sorted(table)):
        shape, kind, scale = table[name]
        if kind == "normal":
            out[name] = jax.random.normal(jax.random.fold_in(key, i), shape,
                                          jnp.float32) * scale
        elif kind == "ones":
            out[name] = jnp.ones(shape, jnp.float32)
        elif kind == "zeros":
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            raise ValueError(f"{name}: unknown init {kind!r}")
    return out


def fan_in_scale(fan_in: int) -> float:
    return 1.0 / math.sqrt(fan_in)


def _scaled_round(x, dtype, largest):
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / largest, 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def fp8(x):
    return _scaled_round(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, g):
    return (_scaled_round(g, jnp.float8_e5m2, 57344.0),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


def matmul(precision: str):
    """An einsum at the given precision (``f32`` or the ``fp8`` control)."""
    if precision == "f32":
        def mm(eq, a, b):
            return jnp.einsum(eq, a, b, precision=HIGHEST)
    elif precision == "fp8":
        def mm(eq, a, b):
            return jnp.einsum(eq, fp8(a), fp8(b), precision=HIGHEST)
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return mm


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def xent_sum(logits, labels):
    """Summed cross entropy over labels >= 0, and how many there were."""
    keep = labels >= 0
    lab = jnp.where(keep, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(keep, lse - picked, 0.0)), jnp.sum(keep)


def adamw(params, mu, nu, grads, step, lr):
    """One AdamW step with global-norm clipping; ``step`` counts from 1.
    Returns (params, mu, nu, clipped grads)."""
    a = ADAM
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, a["clip_norm"] / (gnorm + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    mu = jax.tree.map(lambda m, g: a["b1"] * m + (1 - a["b1"]) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: a["b2"] * v + (1 - a["b2"]) * g * g, nu, grads)
    c1 = 1 - a["b1"] ** step
    c2 = 1 - a["b2"] ** step
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + a["eps"])
                                  + a["weight_decay"] * p),
        params, mu, nu)
    return params, mu, nu, grads


def leaf_norms(tree) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def train_readings(module, model: dict, seed: int, stream, *, steps: int,
                   lr: float, rows: int, precision: str = "f32",
                   device=None) -> dict:
    """Train ``steps`` plain steps from the seed's weights on ``stream``'s
    batches, in blocks of ``rows`` rows, on one device.

    Returns the readings ``correct`` compares: each step's mean loss,
    each leaf's norm of the first (clipped) gradient, and each leaf's
    norm of the change of the parameters over all the steps.
    """
    device = device or jax.devices()[0]
    mm = matmul(precision)
    table = module.param_table(model)
    init = jax.jit(lambda k: init_params(table, k), out_shardings=SingleDeviceSharding(device))
    key = seed_key(seed)

    def block(params, tokens, labels):
        with jax.default_matmul_precision("highest"):
            return module.loss_sum(params, tokens, labels, model, mm)

    vg = jax.jit(jax.value_and_grad(block, has_aux=True))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))
    update = jax.jit(
        lambda p, m, v, g, n, step: adamw(
            p, m, v, jax.tree.map(lambda x: x / n, g), step, lr),
        donate_argnums=(0, 1, 2, 3))
    norms = jax.jit(leaf_norms)
    change = jax.jit(lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a, b)))

    params = init(key)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for step in range(steps):
        batch = stream.sample(step)
        total, count, grads = 0.0, 0, None
        for r0 in range(0, batch["tokens"].shape[0], rows):
            tok = jax.device_put(batch["tokens"][r0:r0 + rows], device)
            lab = jax.device_put(batch["labels"][r0:r0 + rows], device)
            (s, c), g = vg(params, tok, lab)
            grads = g if grads is None else add(grads, g)
            total += float(s)
            count += int(c)
        losses.append(total / count)
        params, mu, nu, clipped = update(params, mu, nu, grads,
                                         jnp.float32(count), jnp.float32(step + 1))
        if step == 0:
            grad_norms = {k: float(v) for k, v in norms(clipped).items()}
        del clipped
    moved = {k: float(v) for k, v in change(params, init(key)).items()}
    return {"loss": losses, "grad": grad_norms, "update": moved}


def check_layout(table: dict, shapes: dict) -> None:
    """Fail unless the reference's leaves are the program's, shape for shape."""
    ours = {k: tuple(v[0]) for k, v in table.items()}
    theirs = {k: tuple(v.shape) for k, v in shapes.items()}
    if ours != theirs:
        diff = sorted(set(ours.items()) ^ set(theirs.items()))
        raise ValueError(f"reference and program parameter layouts differ: {diff}")
    bad = [k for k, v in shapes.items() if np.dtype(v.dtype) != np.float32]
    if bad:
        raise ValueError(f"program parameters not float32: {bad}")


def head_loss(y, w, labels, mm):
    """Cross entropy of the output head, a sequence chunk at a time."""
    B, S, d = y.shape
    chunk = min(HEAD_CHUNK, S)
    if S % chunk:
        chunk = S

    @jax.checkpoint
    def body(acc, xs):
        yc, lc = xs
        s, c = xent_sum(mm("bsd,dv->bsv", yc, w), lc)
        return (acc[0] + s, acc[1] + c), None

    ys = jnp.moveaxis(y.reshape(B, S // chunk, chunk, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, S // chunk, chunk), 1, 0)
    (total, count), _ = jax.lax.scan(body, (jnp.float32(0), jnp.int32(0)), (ys, ls))
    return total, count
