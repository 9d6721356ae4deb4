"""Ragged decode attention on the serving path.

Kernel level: the length-aware Pallas decode kernel vs the dense oracle on
mixed-length batches (including length-0 / freshly-freed rows and all-full
rows), non-dividing cache lengths (the old ``C % bc`` AssertionError), and
the STRUCTURAL block-skip guarantee — executed KV blocks per row must be
ceil(length/bc), not C/bc (counted, not timed: CI is CPU interpret mode).

Engine level: decoded tokens are bit-identical with the kernel wired in
(attn_impl="decode_kernel", the default) vs the dense SDPA path
(attn_impl="xla") on a mixed-occupancy batch, and a request generating
past max_context terminates cleanly (LENGTH_CAPPED) instead of clobbering
its last KV row.

Model level: the decode cost model is linear in the occupancy histogram's
mean context, and the DEP shared-expert emission honors the solved order.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import DepClusterConfig
from repro.core import PAPER_A6000, FinDEPPlanner
from repro.core import dep
from repro.core.planner import PlannerConfig
from repro.kernels.decode_attention.kernel import (decode_attention_pallas,
                                                   largest_block_size)
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.runtime import Request, RequestState, ServingEngine
from repro.sched import OccupancySummary

KEY = jax.random.PRNGKey(7)


def _qkv(B, C, H, Kv, D, dtype=jnp.float32):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Kv, C, D), dtype)
    v = jax.random.normal(ks[2], (B, Kv, C, D), dtype)
    return q, k, v


def _tol(dt):
    return dict(rtol=2e-2, atol=5e-2) if dt == jnp.bfloat16 else \
        dict(rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# kernel: ragged parity + shapes + block skip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_parity_mixed_lengths(dtype):
    """Mixed lengths including 0 (freshly-freed slot) and C (full row)."""
    B, C, H, Kv, D = 6, 512, 8, 2, 64
    q, k, v = _qkv(B, C, H, Kv, D, dtype)
    lengths = jnp.asarray([0, 1, 37, 128, 300, 512], jnp.int32)
    y = decode_attention_pallas(q, k, v, lengths, bc=128)
    r = decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(r, np.float32), **_tol(dtype))
    # a freed row's output is exact zeros, not the mean of V
    assert float(jnp.max(jnp.abs(y[0]))) == 0.0


def test_kernel_parity_under_jit_and_ops_wrapper():
    B, C, H, Kv, D = 4, 256, 4, 4, 32
    q, k, v = _qkv(B, C, H, Kv, D)
    lengths = jnp.asarray([5, 64, 200, 256], jnp.int32)
    r = decode_attention_ref(q, k, v, lengths)
    y = jax.jit(lambda *a: decode_attention_pallas(*a, bc=64))(q, k, v,
                                                              lengths)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), rtol=1e-5,
                               atol=1e-5)
    y2 = decode_attention(q, k, v, lengths, bc=64)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(r), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("C,bc,expect_bc", [(600, 512, 200), (384, 512, 384),
                                            (384, 128, 128)])
def test_kernel_nondividing_cache_lengths(C, bc, expect_bc):
    """C % bc != 0 used to raise AssertionError after bc = min(bc, C);
    now the kernel runs at the largest block size dividing C that the
    TPU can tile (a multiple of the 8-row float32 sublane tile)."""
    assert largest_block_size(C, bc) == expect_bc
    B, H, Kv, D = 3, 4, 2, 32
    q, k, v = _qkv(B, C, H, Kv, D)
    lengths = jnp.asarray([1, C // 2, C], jnp.int32)
    y = decode_attention_pallas(q, k, v, lengths, bc=bc)
    r = decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), rtol=1e-5,
                               atol=1e-5)
    # and through the jit'd public wrapper
    y2 = decode_attention(q, k, v, lengths, bc=bc)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(r), rtol=1e-5,
                               atol=1e-5)


def test_kernel_block_skip_counts():
    """Structural acceptance: executed KV blocks per row proportional to
    ceil(length/bc), NOT C/bc (counted — interpret mode has no wall
    clock worth timing)."""
    B, C, H, Kv, D, bc = 5, 1024, 4, 2, 32, 128
    q, k, v = _qkv(B, C, H, Kv, D)
    lengths = jnp.asarray([0, 1, 130, 512, 1024], jnp.int32)
    _, counts = decode_attention_pallas(q, k, v, lengths, bc=bc,
                                        return_block_counts=True)
    counts = np.asarray(counts)                        # [B, Kv]
    expect = [-(-int(l) // bc) for l in lengths]       # ceil(l/bc)
    for kv in range(Kv):
        assert list(counts[:, kv]) == expect, (counts, expect)
    total = C // bc
    # short rows really skip: far fewer executed blocks than the cache has
    assert counts[1].max() == 1 < total
    assert counts[2].max() == 2 < total
    assert counts[4].max() == total


def test_ops_pathological_length_falls_back_to_ref():
    """A prime cache length has no tile-aligned divisor. Within one block
    (C <= bc) the kernel streams all of C as a single block, which the
    TPU tiles as the whole dim; past one block the wrapper refuses
    instead of quietly running the oracle, and ``use_kernel=False`` is
    the explicit oracle path."""
    B, C, H, Kv, D = 2, 127, 4, 2, 32
    q, k, v = _qkv(B, C, H, Kv, D)
    lengths = jnp.asarray([50, 127], jnp.int32)
    r = decode_attention_ref(q, k, v, lengths)
    for y in (decode_attention(q, k, v, lengths),
              decode_attention(q, k, v, lengths, bc=64, use_kernel=False)):
        np.testing.assert_allclose(np.asarray(y), np.asarray(r), rtol=1e-5,
                                   atol=1e-5)
    with pytest.raises(ValueError, match="cannot tile"):
        decode_attention(q, k, v, lengths, bc=64)


# ---------------------------------------------------------------------------
# engine: kernel on the serving path
# ---------------------------------------------------------------------------

def _serve_mixed(attn_impl, prompts, max_new=6):
    cfg = get_smoke_config("qwen2-1.5b")
    eng = ServingEngine(cfg, num_slots=3, max_context=128,
                        attn_impl=attn_impl, dtype=jnp.float32, seed=0)
    reqs = [Request(prompt=p, max_new_tokens=max_new) for p in prompts]
    # staggered arrivals => mixed occupancy (slots at different contexts)
    eng.submit(reqs[0])
    eng.step()
    eng.step()
    for r in reqs[1:]:
        eng.submit(r)
    while eng.step() or eng.waiting:
        pass
    assert all(r.state == RequestState.FINISHED for r in reqs)
    return [r.output for r in reqs]


def test_engine_tokens_identical_with_and_without_kernel():
    """Acceptance: wiring the ragged kernel into the decode path must not
    change a single decoded token on a mixed-occupancy batch."""
    rng = np.random.RandomState(0)
    cfg = get_smoke_config("qwen2-1.5b")
    prompts = [list(rng.randint(0, cfg.vocab_size, size=n))
               for n in (4, 21, 50)]
    assert _serve_mixed("xla", prompts) == \
        _serve_mixed("decode_kernel", prompts)


def test_engine_finishes_request_at_kv_cap():
    """A request generating past max_context terminates cleanly
    (LENGTH_CAPPED) instead of clobbering the last cache row forever."""
    cfg = get_smoke_config("qwen2-1.5b")
    C = 32
    rng = np.random.RandomState(1)
    prompt = list(rng.randint(0, cfg.vocab_size, size=8))

    eng = ServingEngine(cfg, num_slots=1, max_context=C, dtype=jnp.float32,
                        seed=0)
    req = Request(prompt=prompt, max_new_tokens=10_000)
    eng.submit(req)
    steps = 0
    while eng.step() or eng.waiting:
        steps += 1
        assert steps < 200, "engine did not terminate at the KV cap"
        # the ledger never counts past max_context between steps
        assert all(eng.kv.length(s) <= C for s in eng.kv.live_slots())
    assert req.state == RequestState.LENGTH_CAPPED
    # output stops exactly at the cap: the slot's context (prompt + output)
    # fills all C cache rows, each written once
    assert len(req.output) == C - len(prompt) + 1
    assert eng.kv.live_count() == 0                 # slot freed

    # the tokens up to the cap are exactly what an uncapped-length request
    # would have produced — the cap ends generation, it does not corrupt it
    eng2 = ServingEngine(cfg, num_slots=1, max_context=C, dtype=jnp.float32,
                         seed=0)
    req2 = Request(prompt=prompt, max_new_tokens=C - len(prompt) + 1)
    eng2.submit(req2)
    eng2.run()
    assert req2.state == RequestState.FINISHED
    assert req2.output == req.output
    # the last cache row holds the same (single-write) KV in both runs
    for c1, c2 in zip(eng.kv.caches, eng2.kv.caches):
        if isinstance(c1, dict) and "k" in c1:
            np.testing.assert_array_equal(np.asarray(c1["k"][0, :, C - 1]),
                                          np.asarray(c2["k"][0, :, C - 1]))


# ---------------------------------------------------------------------------
# DEP shared-expert order (replicated-token decode path)
# ---------------------------------------------------------------------------

def test_shared_schedule_honors_solved_order():
    """ASAS lowers the shared expert as r2 segments at chunk boundaries;
    AASS as one whole-batch task at boundary 0 — the executor walk emits
    exactly those segments (the replicated decode path used to silently
    emit AASS placement for ASAS plans)."""
    from repro.core import taskgraph as tg

    x = jnp.arange(30.0).reshape(10, 3)
    calls = []

    def fn(seg):
        calls.append(int(seg.shape[0]))
        return seg * 2.0

    graph = tg.lower_exec(4, "ASAS")
    shared = [t for t in graph.exec_walk() if t.kind == tg.SHARED]
    assert [t.chunk for t in shared] == [0, 1, 2, 3]
    parts = [dep._shared_part(fn, x, t.chunk, graph.shared_segments)
             for t in shared]
    assert calls == [2, 2, 2, 4]                  # 10 rows over 4 chunks
    np.testing.assert_allclose(np.asarray(jnp.concatenate(parts, axis=0)),
                               np.asarray(x * 2.0))

    calls.clear()
    graph = tg.lower_exec(4, "AASS")
    shared = [t for t in graph.exec_walk() if t.kind == tg.SHARED]
    assert [t.chunk for t in shared] == [0]       # whole batch at chunk 0
    part = dep._shared_part(fn, x, 0, graph.shared_segments)
    assert calls == [10]
    np.testing.assert_allclose(np.asarray(part), np.asarray(x * 2.0))


# ---------------------------------------------------------------------------
# decode cost model: occupancy-proportional
# ---------------------------------------------------------------------------

def _mk_planner():
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    cluster = DepClusterConfig(num_devices=8, ag=3, eg=5)
    return FinDEPPlanner(cfg, cluster, PAPER_A6000,
                         PlannerConfig(mem_cap_samples=8))


def test_occupancy_mean_std_context():
    occ = OccupancySummary.from_lengths([10, 70, 70, 500], max_bucket=256)
    # bucketed lengths: 64, 128, 128, 256
    assert occ.mean_context == pytest.approx(144.0)
    var = ((64 - 144) ** 2 + 2 * (128 - 144) ** 2 + (256 - 144) ** 2) / 4
    assert occ.std_context == pytest.approx(var ** 0.5)
    empty = OccupancySummary.from_lengths([])
    assert empty.mean_context == 0.0 and empty.std_context == 0.0


def test_decode_attention_term_linear_in_context():
    """The decode attention workload grows linearly with the histogram's
    mean context (the ragged kernel streams ceil(len/bc) blocks per row),
    replacing the prefill-style S^2 term."""
    planner = _mk_planner()
    hw = planner.hardware
    spec1 = planner.stage_models(1, decode_context=256.0)
    spec2 = planner.stage_models(1, decode_context=512.0)
    nh = spec1.spec.n_heads
    dd = spec1.spec.d_k + spec1.spec.d_v
    assert spec2.t_a.beta - spec1.t_a.beta == pytest.approx(
        hw.attn.beta * 256.0 * nh * dd)


def test_decode_plan_makespan_tracks_occupancy():
    """plan_for_occupancy: makespan is monotone in mean context and far
    below the old prefill-style projection (which modeled a full sequence
    per live slot)."""
    planner = _mk_planner()
    occ_lo = OccupancySummary.from_lengths([128] * 4)
    occ_hi = OccupancySummary.from_lengths([2048] * 4)
    p_lo = planner.plan_for_occupancy(occ_lo)
    p_hi = planner.plan_for_occupancy(occ_hi)
    assert p_hi.makespan > p_lo.makespan
    proj = planner.plan(occ_hi.seq_bucket, occ_hi.live)
    assert p_hi.makespan < proj.makespan
    # heterogeneous composition widens the context estimate (same mean)
    occ_mix = OccupancySummary.from_lengths([64, 64, 2048, 2048])
    mid = (occ_mix.mean_context
           + occ_mix.std_context / np.sqrt(occ_mix.live))
    assert mid > occ_mix.mean_context
