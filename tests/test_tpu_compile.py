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
and check the compiled HLO holds the Mosaic kernel. It also compiles the
engine's own decode step at the benchmark's cache shapes and checks that
the step writes its new KV rows in place: no copy of a whole cache, and
every cache output aliased to its donated input. No chip is needed.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and under several
test workers the one that runs this file takes it.
"""
import collections
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest

import repro.kernels
from repro.configs import get_config
from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas, latent_decode_attention_pallas,
    paged_decode_attention_pallas)
from repro.runtime import ServingEngine

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


#: (layers, slots, context) of each benchmark configuration's decode step,
#: cut to one or two layers (deepseek-v2-lite's first is dense)
STEPS = {"qwen2-moe-a2.7b": (1, 16, 2048),
         "deepseek-v2-lite": (2, 64, 5120)}


def _entry_params(hlo):
    """{parameter number: dimensions} of the HLO's entry computation."""
    entry = hlo.split("\nENTRY ", 1)[1].split("\n}\n", 1)[0]
    return {int(n): s for s, n in re.findall(
        r"= \w+(\[[\d,]*\])\{[^}]*\} parameter\((\d+)\)", entry)}


@pytest.mark.parametrize("model", sorted(STEPS))
def test_decode_step_writes_caches_in_place(one_chip, monkeypatch, model):
    """The engine's jitted decode step at a benchmark cell's cache shapes
    holds no copy of a whole cache, and each cache output aliases its
    donated input: a step writes its new rows into the caches in place."""
    monkeypatch.setattr(repro.kernels, "on_tpu", lambda: True)
    layers, B, C = STEPS[model]
    cfg = dataclasses.replace(get_config(model), num_layers=layers)
    bf16 = jnp.bfloat16
    eng = ServingEngine(cfg, {}, num_slots=B, max_context=C, dtype=bf16,
                        metrics=False, telemetry=False)
    params = jax.eval_shape(lambda k: eng.model.init(k, dtype=bf16),
                            jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: [
        dict(c, index=jnp.zeros((B,), jnp.int32))
        for c in eng.model.init_cache(B, C, dtype=bf16)])
    on_chip = lambda t: jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip), t)
    vec = lambda dt: _spec((B,), dt, one_chip)
    hlo = eng._decode_jit.lower(
        on_chip(params), _spec((B, 1), jnp.int32, one_chip),
        on_chip(caches), vec(jnp.float32), vec(jnp.int32),
        _spec((2,), jnp.uint32, one_chip), vec(jnp.int32), None,
        use_topk=True).compile().as_text()
    eng.close()
    assert "tpu_custom_call" in hlo
    leaves = [f"[{','.join(map(str, a.shape))}]"
              for a in jax.tree.leaves(caches)]
    whole = {n for n in leaves if n.count(",") == 3}
    copies = [line for line in hlo.splitlines()
              if (m := re.search(r"= (.*?) copy(-start)?\(", line))
              and any(n in m.group(1) for n in whole)]
    assert not copies, copies[:2]
    aliased = [int(n) for n in re.findall(r"\{\d+\}: \((\d+), \{\}",
                                          hlo.splitlines()[0])]
    shapes = _entry_params(hlo)
    assert collections.Counter(shapes[n] for n in aliased) == \
        collections.Counter(leaves)
