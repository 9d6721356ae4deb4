"""Compile the serving path's decode kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see what the TPU's
compiler refuses: blocks that break the (8, 128) tiling, scratch it cannot
lay out, kernels GSPMD cannot partition. These tests lower and compile the
dense and paged decode kernels for one chip of a described ``v5e:2x2``
topology, at qwen2-moe-a2.7b widths (16 heads of 128, one per KV head),
a GQA width (granite-moe-1b-a400m: 16 heads of 64 over 8 KV heads), and
the latent mode at deepseek-v2-lite widths (16 heads over one latent
stream of 512 + 64 columns, padded to 640, whose first 512 are the
values, 5120 positions),
and check the compiled HLO holds the Mosaic kernel. No chip is needed.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and under several
test workers the one that runs this file takes it.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas, latent_decode_attention_pallas,
    paged_decode_attention_pallas)

#: (B, H, Kv, D, C): qwen2-moe-a2.7b and granite-moe-1b-a400m decode
WIDTHS = {"qwen2-moe-a2.7b": (16, 16, 16, 128, 4096),
          "granite-moe-1b-a400m": (16, 16, 8, 64, 4096)}
PAGE = 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("model", sorted(WIDTHS))
@pytest.mark.parametrize("counts", [False, True])
def test_dense_decode_kernel_compiles(one_chip, model, counts):
    B, H, Kv, D, C = WIDTHS[model]
    kv = _spec((B, Kv, C, D), jnp.bfloat16, one_chip)
    fn = jax.jit(lambda q, k, v, n: decode_attention_pallas(
        q, k, v, n, bc=512, interpret=False, return_block_counts=counts))
    compiled = fn.lower(_spec((B, H, D), jnp.bfloat16, one_chip), kv, kv,
                        _spec((B,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("model", sorted(WIDTHS))
@pytest.mark.parametrize("counts", [False, True])
def test_paged_decode_kernel_compiles(one_chip, model, counts):
    B, H, Kv, D, C = WIDTHS[model]
    n_blocks = C // PAGE
    pool = _spec((B * n_blocks + 1, Kv, PAGE, D), jnp.bfloat16, one_chip)
    fn = jax.jit(lambda q, k, v, n, t: paged_decode_attention_pallas(
        q, k, v, n, t, interpret=False, return_block_counts=counts))
    compiled = fn.lower(_spec((B, H, D), jnp.bfloat16, one_chip), pool,
                        pool, _spec((B,), jnp.int32, one_chip),
                        _spec((B, n_blocks), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("counts", [False, True])
def test_latent_decode_kernel_compiles(one_chip, counts):
    B, H, r, W, C = 64, 16, 512, 640, 5120
    fn = jax.jit(lambda q, c, n: latent_decode_attention_pallas(
        q, c, n, dv=r, scale=0.1147, bc=512, interpret=False,
        return_block_counts=counts))
    compiled = fn.lower(_spec((B, H, W), jnp.bfloat16, one_chip),
                        _spec((B, 1, C, W), jnp.bfloat16, one_chip),
                        _spec((B,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
