"""DeepSeek-V2's block as served: MLA with YaRN rope and an absorbed decode
over the latent cache, a dense first layer, routed and shared experts.

The served logits are compared with the benchmark's plain float32
reference (``bench/models/deepseek_v2.py``, which shares no code with the
program), the absorbed decode with the expanded attention it replaces,
and the latent mode of the decode kernel with its jnp oracle.
"""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.kernels.decode_attention.kernel import \
    latent_decode_attention_pallas
from repro.kernels.decode_attention.ref import latent_decode_attention_ref
from repro.models import attention as attn
from repro.runtime import Request, ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def family():
    """The benchmark's reference and system adapter, and a configuration
    with the published structure at test widths: a dense first layer and
    two MoE layers, 4 heads of 16 (no rope) + 8 (rope) and values of 16
    over a latent of 32, YaRN at the published factor."""
    ref = _load("models/deepseek_v2.py", "test_mla_reference")
    system = _load("systems/deepseek_v2.py", "test_mla_system")
    with open(os.path.join(BENCH, "tests", "data", "tiny-dsv2.json")) as f:
        cfg = json.load(f)
    return ref, system, cfg


def _serve(system, cfg, params, steps: int):
    """Greedy-serve one prompt through ``ServingEngine`` (float32, the
    latent decode kernel interpreted) and return the request and the
    decode steps' logits."""
    eng = ServingEngine(system.model_config(cfg), params, num_slots=2,
                        max_context=64, attn_impl="decode_kernel",
                        dtype=jnp.float32)
    prompt = np.random.default_rng(1).integers(0, cfg["vocab_size"], 20)
    req = Request(prompt=prompt.tolist(), max_new_tokens=steps)
    eng.submit(req)
    logits = []
    while eng.step():
        logits.append(np.asarray(eng.last_logits[0]))
    eng.close()
    return req, np.stack(logits)


#: float32 program against the float32 reference: the same sums in
#: another order (a seen gap of 6.7e-6 on logits of magnitude ~4); the
#: same program over weights rounded to bf16 lies 6.6e-2 away
LOGIT_ATOL = 1e-4


@pytest.mark.parametrize("weights", ["float32", "bfloat16"])
def test_served_logits_match_reference(family, weights):
    """Prefill, then 10 decode steps through the latent kernel: each
    step's logits are the reference's full forward at that position,
    within LOGIT_ATOL; the same weights rounded to bf16 fail it."""
    ref, system, cfg = family
    params = ref.make_params(cfg, 5, jnp.float32)
    served = params
    if weights == "bfloat16":
        served = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    req, got = _serve(system, cfg, served, steps=10)
    assert len(got) == 10
    tokens = np.asarray(req.prompt + req.output[:-1], np.int32)
    rows = np.arange(len(req.prompt) - 1, len(tokens))
    want = np.asarray(ref.reference_logits(params, cfg, tokens, rows))
    err = float(np.abs(got - want).max())
    if weights == "float32":
        assert err < LOGIT_ATOL, err
    else:
        assert err > LOGIT_ATOL * 10, err


def _mla_layer(B=3, C=40, seed=0):
    cfg = get_smoke_config("deepseek-v2-lite")
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    params = attn.attention_init(k[0], cfg)
    r, dn, dr, dv = attn.mla_dims(cfg)
    x = jax.random.normal(k[1], (B, C, cfg.d_model), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(C)[None], (B, C))
    q_nope, q_pe, latent = attn._mla_project(params, cfg, x, pos)
    return cfg, params, q_nope, q_pe, latent


@pytest.mark.parametrize("impl", ["decode_kernel", "xla"])
def test_absorbed_decode_matches_expanded(impl):
    """Attention over the latent cache with W_uk folded into the query and
    W_uv into the output equals the prefill's attention over K and V
    expanded through W_kv_b, row by row at ragged lengths (float32; the
    two orders of the same products differ by rounding only)."""
    cfg, params, q_nope, q_pe, latent = _mla_layer()
    B, C = latent.shape[:2]
    lens = jnp.asarray([C, 17, 1])
    last = lens - 1
    got = attn._mla_decode_core(
        params, cfg, q_nope[jnp.arange(B), last],
        q_pe[jnp.arange(B), last], latent[:, None], lens, impl, None)
    q, k, v = attn._mla_expand(params, cfg, q_nope, q_pe, latent)
    mask = jnp.arange(C)[None, None, :] < lens[:, None, None]   # [B,1,C]
    want = attn._sdpa(q[jnp.arange(B), last][:, None], k, v, mask,
                      attn.mla_scale(cfg))[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-5)


def test_decode_program_holds_no_expanded_cache():
    """The served decode step never multiplies the cache by W_kv_b: no
    value of rank four or more in its program spans both the cache's
    positions and the heads (the expansion [B, C, H, dn + dv] would)."""
    cfg = get_smoke_config("deepseek-v2-lite")
    C, H = 40, cfg.num_heads
    eng = ServingEngine(cfg, num_slots=3, max_context=C, dtype=jnp.float32)
    eng.kv.ensure_caches()
    lengths = jnp.asarray([5, 0, 12], jnp.int32)
    jaxpr = jax.make_jaxpr(eng._decode_step)(
        eng.params, eng.last_tokens, eng.kv.caches, eng.temps, eng.top_ks,
        jax.random.PRNGKey(0), lengths)
    shapes = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                continue              # the kernel's blocks are its own
            shapes.extend(tuple(v.aval.shape) for v in eqn.outvars
                          if hasattr(v.aval, "shape"))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    assert any(C in s for s in shapes)          # the latent cache is there
    assert not [s for s in shapes if len(s) >= 4 and C in s and H in s]
    eng.close()


def test_latent_cache_rows_fill_whole_lanes():
    """A latent cache row is r + dr values zero-padded to whole 128-lane
    rows (576 -> 640 at published widths), a width the TPU lays out row
    major, so the decode kernel reads the cache where it lies; serving
    writes the padding as zeros, which the query's zero columns meet."""
    assert attn.mla_cache_width(get_config("deepseek-v2-lite")) == 640
    cfg = get_smoke_config("deepseek-v2-lite")
    r, dr = cfg.mla_kv_lora_rank, cfg.mla_rope_dim
    W = attn.mla_cache_width(cfg)
    assert W % 128 == 0 and r + dr <= W < r + dr + 128
    eng = ServingEngine(cfg, num_slots=2, max_context=32, dtype=jnp.float32)
    eng.submit(Request(prompt=[3, 1, 4, 1, 5], max_new_tokens=4))
    eng.run()
    for c in eng.kv.caches:
        lat = np.asarray(c["latent"])
        assert lat.shape[-1] == W
        assert np.abs(lat[..., :r + dr]).max() > 0
        assert not lat[..., r + dr:].any()
    eng.close()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_latent_kernel_matches_ref_at_ragged_lengths(dtype):
    """Latent mode: one 576-style stream per row (keys all columns, values
    the first ``dv``) for every head; rows at 0, 1, a block edge, mid
    block and the whole cache; the block-skip count follows the length."""
    B, H, Dk, dv, C, bc = 5, 8, 96, 64, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    q = jax.random.normal(ks[0], (B, H, Dk), dtype)
    cache = jax.random.normal(ks[1], (B, 1, C, Dk), dtype)
    lens = jnp.asarray([0, 1, 64, 100, 256], jnp.int32)
    scale = 0.37
    got, counts = latent_decode_attention_pallas(
        q, cache, lens, dv=dv, scale=scale, bc=bc, return_block_counts=True)
    want = latent_decode_attention_ref(q, cache, lens, dv=dv, scale=scale)
    assert got.shape == (B, H, dv)
    # test_kernels.py's tolerances: both accumulate in float32; in bf16
    # the kernel rounds its probabilities to bf16 before the value product
    tol = (dict(rtol=2e-2, atol=5e-2) if dtype == jnp.bfloat16
           else dict(rtol=1e-5, atol=1e-5))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)
    assert np.asarray(got[0]).max() == 0 == np.asarray(got[0]).min()
    assert counts.tolist() == [0, 1, 1, 2, 4]
