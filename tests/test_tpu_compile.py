"""Compile for a described TPU v5e chip, at the widths the models use.

Nothing runs: the TPU compiler, which is installed without a chip,
compiles for devices of a described ``v5e:2x2`` topology and refuses
what the chip would refuse (block shapes off the (8, 128) tiling, a
program over the chip's memory).  Interpret-mode tests cannot see that.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.  Keep these tests in this one file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

V5E_HBM_BYTES = 16e9  # one v5e chip (Google Cloud documentation, "TPU v5e")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep it out of the cache.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_call(name, chip):
    """(fn, argument shapes) of one kernel at its model's widths."""
    from repro.kernels import flash_attention, mlstm_scan, ssd_scan

    f32 = jnp.float32
    if name == "flash_attention":      # stablelm_3b: 32 heads of 80
        B, H, S, D = 8, 32, 2048, 80
        qkv = _sds(chip, (B, H, S, D))
        return flash_attention, (qkv, qkv, qkv)
    if name == "mlstm_scan":           # xlstm_125m: 4 heads of 384
        B, S, H, D = 8, 2048, 4, 384
        qkv = _sds(chip, (B, S, H, D))
        gate = _sds(chip, (B, S, H), f32)
        return mlstm_scan, (qkv, qkv, qkv, gate, gate)
    if name == "ssd_scan":             # zamba2_1p2b: 64 heads of 64, state 64
        B, S, H, P, N = 8, 2048, 64, 64, 64
        return ssd_scan, (_sds(chip, (B, S, H, P)), _sds(chip, (B, S, H), f32),
                          _sds(chip, (H,), f32), _sds(chip, (B, S, N)),
                          _sds(chip, (B, S, N)))
    raise KeyError(name)


@pytest.mark.parametrize("name", ["flash_attention", "mlstm_scan", "ssd_scan"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_call(name, one_chip)
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_xlstm_train_step_fits_one_v5e(topo):
    """xlstm_125m at its published widths, batch 8, sequence 2048: the
    one-chip train step compiles and its arguments plus temporaries fit
    the chip's HBM."""
    from repro.configs import arch_config
    from repro.models import Model
    from repro.parallel.sharding import ShardingContext
    from repro.train.steps import batch_shardings, build_train_step

    cfg = arch_config("xlstm_125m")
    mesh = Mesh(np.asarray(topo.devices[:1], dtype=object).reshape(1, 1),
                ("data", "model"))
    ctx = ShardingContext(mesh=mesh, mode="train")
    step, shardings, abstract = build_train_step(Model(cfg), ctx)
    b_shard = batch_shardings(cfg, ctx, 8, 2048)
    batch = {k: jax.ShapeDtypeStruct((8, 2048), jnp.int32) for k in b_shard}
    compiled = jax.jit(
        step, in_shardings=(shardings, b_shard),
        out_shardings=(shardings, None), donate_argnums=(0,),
    ).lower(abstract, batch).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < V5E_HBM_BYTES, used
