"""Compiles for a described TPU v5e, with no chip attached.

The serving path's Pallas kernel and its whole paged step are compiled by
the TPU compiler at real widths, which refuses what interpret mode cannot
see (tile layouts the kernel compiler does not support, programs that do
not fit the chip). Nothing runs, so these tests say nothing about results
or times.

The topology is described inside a module-scoped fixture and never while
a module is imported: only one process at a time may load the TPU
library, and every test worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attn import paged_attention
from repro.models import Model

HBM_BYTES = 16 * 1024 ** 3        # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("kv,group,dh,dtype,s", [
    (8, 4, 64, jnp.bfloat16, 1),     # llama3.2-1b decode rows
    (8, 4, 64, jnp.bfloat16, 32),    # llama3.2-1b prefill-chunk rows
    (8, 4, 64, jnp.float32, 1),      # the engine's f32 block pool
    (8, 4, 64, jnp.float32, 32),
    (4, 1, 32, jnp.float32, 1),      # nectar-relu-llama-1.7m
    (4, 1, 32, jnp.float32, 32),
    (4, 1, 32, jnp.float32, 5),      # speculative verify rows (K+1)
])
def test_paged_attention_compiles_for_v5e(one_chip, kv, group, dh, dtype,
                                          s):
    B, bs, MB, n_blocks = 8, 16, 128, 1024

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    fn = jax.jit(lambda q, k, v, t, n: paged_attention(
        q, k, v, t, n, block_size=bs))
    compiled = fn.lower(
        sds((B, s, kv * group, dh), dtype),
        sds((n_blocks, bs, kv, dh), dtype), sds((n_blocks, bs, kv, dh), dtype),
        sds((B, MB), jnp.int32), sds((B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_llama_step(one_chip, backend, n_blocks):
    """The llama3.2-1b decode step (S=1, batch 8, max_seq 2048, block 16,
    the engine's f32 pool of ``n_blocks`` blocks) compiled for one chip;
    returns it with the bytes it needs."""
    cfg = get_config("llama3.2-1b")
    model = Model(cfg)
    B, bs, max_seq = 8, 16, 2048
    mb = max_seq // bs

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = placed(jax.eval_shape(
        lambda: model.init_paged_cache(B, n_blocks, bs, mb, jnp.float32)))

    def step(params, tokens, cache, n_valid):
        return model.forward_step(params, tokens, None, cache, n_valid,
                                  None, None, None, bs, backend=backend)

    compiled = jax.jit(step).lower(
        params, placed(jax.ShapeDtypeStruct((B, 1), jnp.int32)), cache,
        placed(jax.ShapeDtypeStruct((B,), jnp.int32))).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return compiled, total


@pytest.mark.parametrize("backend", ["naive", "flash"])
def test_llama_paged_step_fits_one_v5e(one_chip, backend, monkeypatch):
    """The llama3.2-1b decode step at the default pool (batch x
    blocks_per_seq blocks) compiles for one chip and fits its HBM."""
    # the attention call site picks the Pallas interpreter from the
    # process's backend, which is the CPU here: steer it to the chip's
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, total = _compile_llama_step(one_chip, backend, 8 * 128)
    assert total < HBM_BYTES, total
    if backend == "flash":
        assert "tpu_custom_call" in compiled.as_text()


def test_llama_pool_read_step_fits_one_v5e(one_chip):
    """With a pool of a quarter of batch x blocks_per_seq blocks the
    decode step reads the whole pool once a layer: it compiles for one
    chip, fits, and holds no [B*MB, bs, Kv, Dh] gather of the tables."""
    compiled, total = _compile_llama_step(one_chip, "naive", 256)
    assert total < HBM_BYTES, total
    # the gather's [B*MB, bs, Kv, Dh] blocks, in f32 or converted to bf16
    assert "[1024,16,8,64]" not in compiled.as_text()
