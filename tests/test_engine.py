"""Serving engine: continuous batching correctness and lifecycle."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.runtime import Request, RequestState, ServingEngine
from repro.runtime.sampler import sample


def _greedy_ref(model, params, prompt, n, budget=128):
    lg, caches = model.prefill(params, jnp.asarray([prompt]),
                               seq_budget=budget)
    out = []
    cur = jnp.argmax(lg[0, -1]).astype(jnp.int32)[None, None]
    for _ in range(n):
        out.append(int(cur[0, 0]))
        lg, caches = model.decode_step(params, cur, caches)
        cur = jnp.argmax(lg[0, -1]).astype(jnp.int32)[None, None]
    return out


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "recurrentgemma-9b",
                                  "xlstm-1.3b"])
def test_engine_matches_greedy_reference(arch):
    cfg = get_smoke_config(arch)
    # the decode kernel takes full-attention caches; RecurrentGemma's
    # local-attention ring cache is served by the dense path, explicitly
    eng = ServingEngine(cfg, num_slots=3, max_context=128,
                        attn_impl="xla" if cfg.family == "hybrid"
                        else "decode_kernel", dtype=jnp.float32)
    model = build_model(cfg, dtype=jnp.float32)
    prompt = list(np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=7))
    ref = _greedy_ref(model, eng.params, prompt, 8)
    reqs = [Request(prompt=prompt, max_new_tokens=8) for _ in range(3)]
    for r in reqs:
        eng.submit(r)
    while eng.step() or eng.waiting:
        pass
    for r in reqs:
        assert r.state == RequestState.FINISHED
        assert r.output == ref, (r.output, ref)


def test_continuous_batching_staggered_arrivals():
    """Requests arriving mid-decode must not corrupt running slots."""
    cfg = get_smoke_config("qwen2-1.5b")
    eng = ServingEngine(cfg, num_slots=2, max_context=128,
                        dtype=jnp.float32)
    model = build_model(cfg, dtype=jnp.float32)
    rng = np.random.RandomState(1)
    p1 = list(rng.randint(0, cfg.vocab_size, size=5))
    p2 = list(rng.randint(0, cfg.vocab_size, size=9))
    ref1 = _greedy_ref(model, eng.params, p1, 6)
    ref2 = _greedy_ref(model, eng.params, p2, 6)
    r1 = Request(prompt=p1, max_new_tokens=6)
    r2 = Request(prompt=p2, max_new_tokens=6)
    eng.submit(r1)
    eng.step()
    eng.step()
    eng.submit(r2)          # lands in the other slot mid-flight
    while eng.step() or eng.waiting:
        pass
    assert r1.output == ref1
    assert r2.output == ref2


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "deepseek-v2-lite"])
def test_decode_step_takes_caches_donated(arch):
    """The decode step takes the KV caches donated: after a step every
    cache array it was handed is deleted and the engine holds the step's
    own. Rows written in place, each at its slot's own position (prompts
    of 7, 3 and 11 tokens), serve the greedy reference's tokens, greedy
    and under top-k 1 at a high temperature. The reference decodes one
    row at a time, so the MoE drops no token (capacity factor E/k)."""
    cfg = get_smoke_config(arch)
    if cfg.is_moe:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=cfg.moe.num_experts / cfg.moe.top_k))
    eng = ServingEngine(cfg, num_slots=3, max_context=64,
                        dtype=jnp.float32)
    model = build_model(cfg, dtype=jnp.float32)
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(0, cfg.vocab_size, size=n))
               for n in (7, 3, 11)]
    refs = [_greedy_ref(model, eng.params, p, 6, budget=64)
            for p in prompts]
    reqs = [Request(prompt=p, max_new_tokens=6, temperature=t, top_k=k)
            for p, (t, k) in zip(prompts, [(0.0, 0), (5.0, 1), (0.0, 0)])]
    handed = []
    decode = eng._decode_jit

    def spy(params, tokens, caches, *args, **kw):
        handed.extend(jax.tree.leaves(caches))
        return decode(params, tokens, caches, *args, **kw)

    eng._decode_jit = spy
    for r in reqs:
        eng.submit(r)
    while not handed:
        eng.step()
    assert all(a.is_deleted() for a in handed)
    assert not any(a.is_deleted() for a in jax.tree.leaves(eng.kv.caches))
    while eng.step() or eng.waiting:
        pass
    assert [r.output for r in reqs] == refs
    eng.close()


def test_more_requests_than_slots():
    cfg = get_smoke_config("qwen2-1.5b")
    eng = ServingEngine(cfg, num_slots=2, max_context=64, dtype=jnp.float32)
    rng = np.random.RandomState(2)
    reqs = [Request(prompt=list(rng.randint(0, cfg.vocab_size, size=4)),
                    max_new_tokens=3) for _ in range(5)]
    for r in reqs:
        eng.submit(r)
    while eng.step() or eng.waiting:
        pass
    assert all(r.state == RequestState.FINISHED for r in reqs)
    assert all(len(r.output) == 3 for r in reqs)
    assert eng.stats.decode_tokens == 15


def test_run_returns_finished_requests():
    """run() must return the requests evicted during the call (it used to
    always return [])."""
    cfg = get_smoke_config("qwen2-1.5b")
    eng = ServingEngine(cfg, num_slots=2, max_context=64, dtype=jnp.float32)
    rng = np.random.RandomState(3)
    reqs = [Request(prompt=list(rng.randint(0, cfg.vocab_size, size=4)),
                    max_new_tokens=2) for _ in range(3)]
    for r in reqs:
        eng.submit(r)
    finished = eng.run()
    assert sorted(r.request_id for r in finished) == \
        sorted(r.request_id for r in reqs)
    assert all(r.state == RequestState.FINISHED for r in finished)
    assert eng.run() == []          # nothing new finished on a drained engine


def test_sampler_greedy_vs_temperature():
    key = jax.random.PRNGKey(0)
    logits = jnp.asarray([[0.0, 5.0, 1.0], [3.0, 0.0, 0.0]])
    t0 = sample(key, logits, jnp.zeros(2))
    np.testing.assert_array_equal(np.asarray(t0), [1, 0])
    # high temperature: sampled tokens valid
    t1 = sample(key, logits, jnp.full((2,), 5.0))
    assert t1.shape == (2,)
    assert bool(jnp.all((t1 >= 0) & (t1 < 3)))
    # top-k=1 equals greedy regardless of temperature
    tk = sample(key, logits, jnp.full((2,), 5.0), top_k=1)
    np.testing.assert_array_equal(np.asarray(tk), [1, 0])


# ---------------------------------------------------------------------------
# construction: decode implementation settled, weights in the serving dtype
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,kw", [
    ("recurrentgemma-9b", {}),                 # local-attention ring cache
    ("qwen2-1.5b", {"max_context": 1021}),     # prime C > bc: cannot tile
])
def test_decode_kernel_refuses_at_construction(arch, kw):
    """attn_impl="decode_kernel" is settled when the engine is built: a
    model or cache shape the kernel cannot take raises there, instead of
    quietly serving through the dense path."""
    cfg = get_smoke_config(arch)
    kw = dict({"max_context": 64}, **kw)
    with pytest.raises(ValueError):
        ServingEngine(cfg, num_slots=2, dtype=jnp.float32, **kw)
    # the dense path stays available when asked for by name
    ServingEngine(cfg, num_slots=2, attn_impl="xla", dtype=jnp.float32,
                  **kw).close()


def test_decode_kernel_serves_mla():
    """An MLA model builds with attn_impl="decode_kernel" (the latent
    cache is one KV head the kernel streams) and serves every request,
    token for token as the dense path does; the KV counters read the
    latent cache's bytes and the positions its decode steps attended."""
    cfg = get_smoke_config("deepseek-v2-lite")
    outs = []
    for impl in ("decode_kernel", "xla"):
        eng = ServingEngine(cfg, num_slots=2, max_context=64,
                            attn_impl=impl, dtype=jnp.float32, seed=4)
        reqs = [Request(prompt=[3, 1, 4, 1, 5], max_new_tokens=4),
                Request(prompt=[2, 7], max_new_tokens=3)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        assert all(r.state == RequestState.FINISHED for r in reqs)
        outs.append([r.output for r in reqs])
        snap = eng.metrics.snapshot()
        # 2 slots x 64 positions x r + dr float32 values a layer, padded
        # to whole 128-lane rows, and the per-slot index
        width = -(-(cfg.mla_kv_lora_rank + cfg.mla_rope_dim) // 128) * 128
        assert snap["repro_engine_kv_cache_bytes"] == cfg.num_layers * (
            2 * 64 * width * 4 + 2 * 4)
        # decode steps attend 5..8 and 2..4 positions (prompt length,
        # then one more a step)
        assert snap["repro_engine_decode_kv_tokens_total"] == \
            sum(range(5, 9)) + sum(range(2, 5))
        eng.reset_stats()
        snap = eng.metrics.snapshot()
        assert snap["repro_engine_decode_kv_tokens_total"] == 0
        assert snap["repro_engine_kv_cache_bytes"] > 0
        eng.close()
    assert outs[0] == outs[1]


def test_engine_weights_in_serving_dtype():
    """An engine's own weights are held in its dtype, cast once at
    construction, with Model.init's values: exact in float32, and
    within bf16 rounding (2^-9 relative, 2^-8 where the jitted
    initializer's float32 scaling rounds one ulp apart) otherwise."""
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    ref = build_model(cfg, dtype=jnp.float32).init(jax.random.PRNGKey(3))
    eng = ServingEngine(cfg, num_slots=2, max_context=64,
                        dtype=jnp.bfloat16, seed=3)
    assert {a.dtype for a in jax.tree.leaves(eng.params)} == \
        {jnp.dtype(jnp.bfloat16)}
    for a, b in zip(jax.tree.leaves(eng.params), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                   rtol=2 ** -8, atol=0)
    eng.close()
    eng32 = ServingEngine(cfg, num_slots=2, max_context=64,
                          dtype=jnp.float32, seed=3)
    assert all(bool(jnp.array_equal(a, b)) for a, b in
               zip(jax.tree.leaves(eng32.params), jax.tree.leaves(ref)))
    eng32.close()


def test_compile_cache_helper_defers_to_environment(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache and the helper
    sets nothing; otherwise the cache is the repository's fixed
    .jax_cache (asserted on the path, not turned on in tests)."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    assert str(compile_cache.REPO_CACHE_DIR) == os.path.join(root,
                                                            ".jax_cache")
