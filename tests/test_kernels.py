"""Pallas kernel validation: interpret-mode vs pure-jnp oracles.

Every call passes ``interpret=True``: the wrappers compile for the TPU
unless told otherwise (``tests/test_tpu_compile.py`` compiles them).

Shape/dtype sweeps + property-based gate/mask behavior.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import flash_attention, mlstm_scan, ssd_scan
from repro.kernels.ref import attention_ref, mlstm_ref, ssd_ref

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ------------------------------------------------------------- flash attn --
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,KV,Sq,Sk,D,bq,bk",
    [
        (1, 2, 2, 128, 128, 64, 64, 64),     # MHA square
        (2, 8, 2, 128, 128, 64, 32, 64),     # GQA group=4
        (1, 4, 1, 64, 256, 32, 64, 64),      # MQA, cross lengths
        (2, 3, 3, 96, 96, 16, 32, 32),       # head dim 16, odd blocks
    ],
)
def test_flash_attention_shapes(B, H, KV, Sq, Sk, D, bq, bk, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = rand(ks[0], (B, H, Sq, D), dtype)
    k = rand(ks[1], (B, KV, Sk, D), dtype)
    v = rand(ks[2], (B, KV, Sk, D), dtype)
    causal = Sq == Sk
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **TOL[dtype]
    )


@pytest.mark.parametrize("window", [16, 64, 128])
def test_flash_attention_window(window):
    ks = jax.random.split(jax.random.key(1), 3)
    q = rand(ks[0], (1, 2, 128, 32), jnp.float32)
    k = rand(ks[1], (1, 2, 128, 32), jnp.float32)
    v = rand(ks[2], (1, 2, 128, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window, block_q=32, block_k=32, interpret=True)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_attention_softcap():
    ks = jax.random.split(jax.random.key(2), 3)
    q = rand(ks[0], (1, 2, 64, 32), jnp.float32) * 4
    k = rand(ks[1], (1, 2, 64, 32), jnp.float32) * 4
    v = rand(ks[2], (1, 2, 64, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=True, softcap=20.0, block_q=32, block_k=32, interpret=True)
    ref = attention_ref(q, k, v, causal=True, softcap=20.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-5)


@given(
    seed=st.integers(0, 1000),
    logsq=st.integers(5, 8),
    group=st.sampled_from([1, 2, 4]),
)
@settings(max_examples=10, deadline=None)
def test_flash_attention_property(seed, logsq, group):
    """Random shapes: kernel == oracle, and each output row is a convex
    combination of V rows (|out| <= max |v|)."""
    S = 2 ** logsq
    KV, D = 2, 32
    ks = jax.random.split(jax.random.key(seed), 3)
    q = rand(ks[0], (1, KV * group, S, D), jnp.float32)
    k = rand(ks[1], (1, KV, S, D), jnp.float32)
    v = rand(ks[2], (1, KV, S, D), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    assert float(jnp.max(jnp.abs(out))) <= float(jnp.max(jnp.abs(v))) + 1e-4


# -------------------------------------------------------------------- ssd --
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,P,N,chunk",
    [
        (1, 64, 2, 16, 8, 16),
        (2, 128, 3, 16, 8, 32),
        (1, 128, 1, 32, 16, 64),
        (2, 96, 2, 8, 4, 32),
    ],
)
def test_ssd_shapes(B, S, H, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.key(3), 5)
    x = rand(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = rand(ks[3], (B, S, N), dtype)
    Cm = rand(ks[4], (B, S, N), dtype)
    y = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, _ = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32),
        rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
        atol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
    )


def test_ssd_chunked_matches_model_oracle():
    """The kernel, the model's chunked jnp path, and the sequential
    recurrence must all agree."""
    from repro.models.ssm import ssd_chunked

    ks = jax.random.split(jax.random.key(4), 5)
    B, S, H, P, N = 2, 64, 2, 16, 8
    x = rand(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = rand(ks[3], (B, S, N), jnp.float32)
    Cm = rand(ks[4], (B, S, N), jnp.float32)
    y_seq, st_seq = ssd_ref(x, dt, A, Bm, Cm)
    y_chk, st_chk = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
    y_ker = ssd_scan(x, dt, A, Bm, Cm, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y_chk), np.asarray(y_seq), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_seq), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st_chk), np.asarray(st_seq), rtol=1e-4, atol=1e-4)


@given(seed=st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_ssd_decay_property(seed):
    """With very negative A (fast decay), output ~ local: dt*C.B*x only."""
    ks = jax.random.split(jax.random.key(seed), 5)
    B, S, H, P, N = 1, 32, 1, 8, 4
    x = rand(ks[0], (B, S, H, P), jnp.float32)
    dt = jnp.ones((B, S, H)) * 0.5
    A = jnp.full((H,), -50.0)   # state dies between steps
    Bm = rand(ks[3], (B, S, N), jnp.float32)
    Cm = rand(ks[4], (B, S, N), jnp.float32)
    y = ssd_scan(x, dt, A, Bm, Cm, chunk=8, interpret=True)
    local = jnp.einsum("bsn,bsn->bs", Cm, Bm)[:, :, None, None] * 0.5 * x
    np.testing.assert_allclose(np.asarray(y), np.asarray(local), rtol=1e-3, atol=1e-3)


# ------------------------------------------------------------------ mlstm --
@pytest.mark.parametrize(
    "B,S,H,D,chunk",
    [(1, 64, 2, 16, 16), (2, 128, 2, 16, 32), (1, 96, 1, 32, 32)],
)
def test_mlstm_shapes(B, S, H, D, chunk):
    ks = jax.random.split(jax.random.key(5), 5)
    q = rand(ks[0], (B, S, H, D), jnp.float32)
    k = rand(ks[1], (B, S, H, D), jnp.float32)
    v = rand(ks[2], (B, S, H, D), jnp.float32)
    ig = jax.random.normal(ks[3], (B, S, H))
    fg = jax.random.normal(ks[4], (B, S, H)) + 1.0
    h = mlstm_scan(q, k, v, ig, fg, chunk=chunk, interpret=True)
    hr = mlstm_ref(q, k, v, ig, fg)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=2e-4, atol=2e-4)


def test_mlstm_matches_model_chunked():
    from repro.models.xlstm import mlstm_chunked

    ks = jax.random.split(jax.random.key(6), 5)
    B, S, H, D = 2, 64, 2, 8
    q = rand(ks[0], (B, S, H, D), jnp.float32)
    k = rand(ks[1], (B, S, H, D), jnp.float32)
    v = rand(ks[2], (B, S, H, D), jnp.float32)
    ig = jax.random.normal(ks[3], (B, S, H))
    fg = jax.random.normal(ks[4], (B, S, H)) + 1.0
    h_model, _ = mlstm_chunked(q, k, v, ig, fg, chunk=16)
    h_kernel = mlstm_scan(q, k, v, ig, fg, chunk=16, interpret=True)
    h_seq = mlstm_ref(q, k, v, ig, fg)
    np.testing.assert_allclose(np.asarray(h_model), np.asarray(h_seq), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_kernel), np.asarray(h_seq), rtol=2e-4, atol=2e-4)


@given(seed=st.integers(0, 1000))
@settings(max_examples=8, deadline=None)
def test_mlstm_extreme_gates_stable(seed):
    """Extreme gate preactivations must not produce NaN/Inf (the
    stabilizer state is the whole point)."""
    ks = jax.random.split(jax.random.key(seed), 5)
    B, S, H, D = 1, 32, 1, 8
    q = rand(ks[0], (B, S, H, D), jnp.float32)
    k = rand(ks[1], (B, S, H, D), jnp.float32)
    v = rand(ks[2], (B, S, H, D), jnp.float32)
    ig = jax.random.normal(ks[3], (B, S, H)) * 20    # exp gate up to e^20
    fg = jax.random.normal(ks[4], (B, S, H)) * 20
    h = mlstm_scan(q, k, v, ig, fg, chunk=8, interpret=True)
    assert bool(jnp.all(jnp.isfinite(h)))
    hr = mlstm_ref(q, k, v, ig, fg)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=5e-4, atol=5e-4)
