"""Batch/KV runtime API: batched-prefill parity, KV ledger accounting,
admission policies, rejection, per-slot top-k, and stats lifecycle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.runtime import (ADMISSIONS, BatchScheduler, KVCacheManager,
                           Request, RequestState, ServingEngine,
                           make_admission)
from repro.sched import OccupancySummary, bucket_length


def _tree_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


def _prompts(rng, cfg, sizes):
    return [list(rng.randint(0, cfg.vocab_size, size=n)) for n in sizes]


# ---------------------------------------------------------------------------
# batched prefill parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,sizes", [("qwen2-1.5b", (5, 7, 9)),
                                        ("xlstm-1.3b", (7, 7, 7))])
def test_batched_prefill_matches_sequential_bit_for_bit(arch, sizes):
    """N requests prefilled in ONE batched call must produce per-slot
    caches bit-identical to N sequential single-request prefills, and the
    same generated tokens."""
    cfg = get_smoke_config(arch)
    eng_b = ServingEngine(cfg, num_slots=3, max_context=64,
                          dtype=jnp.float32)
    eng_s = ServingEngine(cfg, params=eng_b.params, num_slots=3,
                          max_context=64, dtype=jnp.float32)
    rng = np.random.RandomState(0)
    prompts = _prompts(rng, cfg, sizes)

    reqs_b = [Request(prompt=p, max_new_tokens=4) for p in prompts]
    for r in reqs_b:
        eng_b.submit(r)
    batched = eng_b._admit()
    assert batched.num_prefilled == 3
    assert len(batched.prefills) == 1          # one same-bucket group

    reqs_s = [Request(prompt=p, max_new_tokens=4) for p in prompts]
    for slot, r in enumerate(reqs_s):
        eng_s._prefill_one(slot, r)

    assert _tree_equal(eng_b.kv.caches, eng_s.kv.caches)
    assert np.array_equal(np.asarray(eng_b.last_tokens),
                          np.asarray(eng_s.last_tokens))
    while eng_b.step() or eng_b.waiting:
        pass
    while eng_s.step() or eng_s.waiting:
        pass
    assert [r.output for r in reqs_b] == [r.output for r in reqs_s]


def test_prefill_last_positions_gathers_per_row_logits():
    """Batched prefill with per-row last_positions must reproduce each
    request's single-prefill final logits."""
    cfg = get_smoke_config("qwen2-1.5b")
    model = build_model(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    prompts = _prompts(rng, cfg, (4, 9, 6))
    bucket = 16
    toks = np.zeros((3, bucket), np.int32)
    for j, p in enumerate(prompts):
        toks[j, :len(p)] = p
    last = np.asarray([len(p) - 1 for p in prompts])
    lg_b, _ = model.prefill(params, jnp.asarray(toks), seq_budget=64,
                            last_positions=last)
    for j, p in enumerate(prompts):
        lg_1, _ = model.prefill(params, jnp.asarray([p]), seq_budget=64)
        # padding changes the shapes XLA reduces over, hence the float32
        # summation order: rows agree to rounding (at most 2.4e-6 on the
        # CPU), not bit for bit. 1e-5 is a few float32 ulps of these
        # logits (|logit| ~ 1) and far below a wrong row's error (~1).
        np.testing.assert_allclose(np.asarray(lg_b[j]),
                                   np.asarray(lg_1[0]), rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# the jitted prefill: parity with the eager model, and its trace counter
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def moe_engine():
    """A float32 engine over the smoke qwen2-moe config with expert-load
    telemetry on, so its prefill also returns the MoE stats."""
    return ServingEngine(get_smoke_config("qwen2-moe-a2.7b"), num_slots=3,
                         max_context=64, dtype=jnp.float32,
                         track_expert_load=True)


@pytest.mark.parametrize("last", [False, True],
                         ids=["bucket_end", "last_positions"])
@pytest.mark.parametrize("rows", [1, 3])
@pytest.mark.parametrize("bucket", [8, 16])
def test_jitted_prefill_matches_eager_model(moe_engine, bucket, rows, last):
    """The engine's one-program prefill returns the eager ``Model.prefill``
    (every operation dispatched on its own, under ``jax.disable_jit``):
    the same logits at the returned rows, the same caches, and the same
    expert loads and dropped count."""
    eng = moe_engine
    rng = np.random.RandomState(bucket * 10 + rows)
    toks = rng.randint(0, eng.cfg.vocab_size,
                       size=(rows, bucket)).astype(np.int32)
    pos = rng.randint(0, bucket, size=rows).astype(np.int32) if last \
        else None
    logits, caches, mstats = eng._prefill_jit(
        eng.params, toks, pos, collect_stats=True, seq_budget=64)
    with jax.disable_jit():
        want, want_caches, want_stats = eng.model.prefill(
            eng.params, jnp.asarray(toks), seq_budget=64,
            last_positions=pos, return_moe_stats=True)
    # one program lets XLA fuse and reorder the float32 sums that the
    # eager path runs as separate operations: values agree to rounding
    # (at most 3.4e-6 here, on the CPU), not bit for bit. 1e-5 is a few
    # float32 ulps of these logits and caches (|x| < 4) and far below
    # the error of a wrong row or position (~1).
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(want[:, 0]), rtol=0, atol=1e-5)
    got_leaves = jax.tree_util.tree_leaves(caches)
    want_leaves = jax.tree_util.tree_leaves(want_caches)
    assert len(got_leaves) == len(want_leaves)
    for g, w in zip(got_leaves, want_leaves):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=0, atol=1e-5)
    assert np.array_equal(np.asarray(mstats.load),
                          np.asarray(want_stats.load))
    assert int(mstats.dropped) == int(want_stats.dropped)


def test_prefill_traces_counter_counts_programs():
    """``repro_engine_prefill_traces_total`` rises by one for a prompt in
    a new bucket, by nothing for another prompt in a bucket already
    compiled, and ``reset_stats()`` clears it."""
    cfg = get_smoke_config("qwen2-1.5b")
    eng = ServingEngine(cfg, num_slots=2, max_context=256,
                        dtype=jnp.float32)
    traces = eng.metrics.counter("repro_engine_prefill_traces_total")
    rng = np.random.RandomState(3)

    def serve(n):
        eng.submit(Request(prompt=list(rng.randint(0, cfg.vocab_size,
                                                   size=n)),
                           max_new_tokens=1))
        eng.run()
        return traces.value

    assert traces.value == 0
    assert serve(10) == 1                  # bucket 64: a new program
    assert serve(40) == 1                  # bucket 64 again: none
    assert serve(100) == 2                 # bucket 128: a new program
    assert eng.metrics.snapshot()["repro_engine_prefill_traces_total"] == 2
    eng.reset_stats()
    assert traces.value == 0
    assert serve(70) == 0                  # bucket 128, compiled before


# ---------------------------------------------------------------------------
# KV ledger accounting
# ---------------------------------------------------------------------------

def test_kv_ledger_alloc_free_occupancy_churn():
    kv = KVCacheManager(num_slots=4, max_context=512)   # ledger-only
    slots = [kv.alloc() for _ in range(4)]
    assert slots == [0, 1, 2, 3]
    assert kv.alloc() is None and kv.free_count() == 0
    for s, n in zip(slots, (10, 70, 200, 500)):
        kv.set_length(s, n)
    occ = kv.occupancy()
    assert occ == OccupancySummary(live=4, hist=((64, 1), (128, 1),
                                                 (256, 1), (512, 1)))
    kv.free(1)
    kv.free(3)
    assert kv.live_slots() == [0, 2] and kv.free_count() == 2
    assert kv.occupancy().hist == ((64, 1), (256, 1))
    with pytest.raises(ValueError):
        kv.free(1)                       # double free
    with pytest.raises(ValueError):
        kv.take(0)                       # already live
    s = kv.alloc()
    assert s == 1                        # lowest free slot reused
    kv.note_decode([0, 2])
    assert kv.length(0) == 11 and kv.length(2) == 201
    assert kv.stats.allocs == 5 and kv.stats.frees == 2
    assert kv.stats.peak_live == 4
    with pytest.raises(ValueError):
        kv.ensure_caches()               # no model behind this ledger


def test_kv_occupancy_caps_at_max_context():
    kv = KVCacheManager(num_slots=2, max_context=128)
    kv.take(0)
    kv.set_length(0, 100_000)
    assert kv.occupancy().hist == ((128, 1),)


# ---------------------------------------------------------------------------
# admission policies
# ---------------------------------------------------------------------------

def _req(n, **kw):
    return Request(prompt=list(range(1, n + 1)), **kw)


def test_admission_order_fcfs_vs_spf():
    waiting = [_req(40), _req(4), _req(20)]
    assert make_admission("fcfs").admit(waiting, 2) == waiting[:2]
    assert make_admission("spf").admit(waiting, 2) == [waiting[1],
                                                       waiting[2]]


def test_admission_token_budget_defers_but_never_starves():
    pol = make_admission("token_budget", token_budget=32)
    waiting = [_req(20), _req(20), _req(20)]
    first = pol.admit(waiting, 3)
    assert first == waiting[:1]          # second would exceed the budget
    # a single prompt larger than the whole budget is still admitted
    huge = [_req(100), _req(4)]
    assert pol.admit(huge, 2) == huge[:1]
    assert "token_budget" in ADMISSIONS


def test_token_budget_caps_every_admission_policy():
    """The step budget binds independent of HOW requests are ranked —
    fcfs/spf with token_budget must not admit unbounded prefill work."""
    kv = KVCacheManager(num_slots=4, max_context=512)
    sched = BatchScheduler(admission="fcfs", token_budget=32)
    waiting = [_req(20), _req(20), _req(20)]
    plan = sched.build_step(waiting, kv)
    assert plan.num_prefilled == 1 and len(waiting) == 2
    assert plan.prefill_tokens <= 32


def test_build_step_groups_by_bucket_and_allocates():
    kv = KVCacheManager(num_slots=4, max_context=512)
    sched = BatchScheduler(admission="fcfs")
    waiting = [_req(10), _req(200), _req(12), _req(100)]
    plan = sched.build_step(waiting, kv)
    assert waiting == []
    assert plan.num_prefilled == 4
    buckets = {g.bucket: len(g.requests) for g in plan.prefills}
    assert buckets == {bucket_length(9): 2, bucket_length(99): 1,
                       bucket_length(199): 1}
    assert buckets == {64: 2, 128: 1, 256: 1}
    assert sorted(s for g in plan.prefills for s in g.slots) == [0, 1, 2, 3]
    assert plan.decode_slots == [0, 1, 2, 3]


def test_token_budget_engine_end_to_end_matches_fcfs():
    """Admission order must not change any request's greedy output —
    chunked-prefill scheduling is a latency policy, not a numerics one."""
    cfg = get_smoke_config("qwen2-1.5b")
    rng = np.random.RandomState(2)
    prompts = _prompts(rng, cfg, (9, 30, 5, 17))

    def serve(**kw):
        eng = ServingEngine(cfg, num_slots=2, max_context=64,
                            dtype=jnp.float32, **kw)
        reqs = [Request(prompt=p, max_new_tokens=4) for p in prompts]
        for r in reqs:
            eng.submit(r)
        eng.run()
        assert all(r.state == RequestState.FINISHED for r in reqs)
        return [r.output for r in reqs]

    base = serve()
    assert serve(admission="token_budget", token_budget=16) == base
    assert serve(admission="spf") == base


# ---------------------------------------------------------------------------
# rejection, top-k, stats
# ---------------------------------------------------------------------------

def test_oversized_prompt_rejected_not_truncated():
    cfg = get_smoke_config("qwen2-1.5b")
    eng = ServingEngine(cfg, num_slots=2, max_context=32, dtype=jnp.float32)
    rng = np.random.RandomState(3)
    ok = Request(prompt=_prompts(rng, cfg, (8,))[0], max_new_tokens=2)
    huge = Request(prompt=_prompts(rng, cfg, (40,))[0], max_new_tokens=2)
    eng.submit(huge)
    eng.submit(ok)
    finished = eng.run()
    assert huge.state == RequestState.REJECTED
    assert huge.error is not None and "max_context" in huge.error
    assert huge.output == [] and huge in finished
    assert ok.state == RequestState.FINISHED and len(ok.output) == 2
    # boundary: the FULL prompt (incl. the decode-fed last token) must fit
    at_cap = Request(prompt=_prompts(rng, cfg, (32,))[0], max_new_tokens=1)
    over_by_one = Request(prompt=_prompts(rng, cfg, (33,))[0],
                          max_new_tokens=1)
    eng.submit(at_cap)
    eng.submit(over_by_one)
    eng.run()
    assert at_cap.state == RequestState.FINISHED
    assert over_by_one.state == RequestState.REJECTED
    # the single-request shim refuses oversized prompts up front too
    with pytest.raises(ValueError, match="max_context"):
        eng._prefill_one(0, Request(prompt=list(range(40))))
    assert eng.kv.free_count() == eng.num_slots      # slot not leaked


def test_request_top_k_respected_per_slot():
    """top_k=1 at high temperature must reproduce the greedy output while
    a plain high-temperature slot diverges — the per-slot top_k vector is
    actually threaded through decode."""
    cfg = get_smoke_config("qwen2-1.5b")
    rng = np.random.RandomState(4)
    prompt = _prompts(rng, cfg, (7,))[0]

    def serve(**kw):
        eng = ServingEngine(cfg, num_slots=1, max_context=64,
                            dtype=jnp.float32, seed=0)
        req = Request(prompt=prompt, max_new_tokens=8, **kw)
        eng.submit(req)
        eng.run()
        return req.output

    greedy = serve()
    assert serve(temperature=5.0, top_k=1) == greedy
    assert serve(temperature=5.0) != greedy


def test_engine_stats_clock_starts_on_work_and_resets():
    cfg = get_smoke_config("qwen2-1.5b")
    eng = ServingEngine(cfg, num_slots=1, max_context=64, dtype=jnp.float32)
    assert eng.stats.start_t is None         # construction != serving
    rng = np.random.RandomState(5)
    eng.submit(Request(prompt=_prompts(rng, cfg, (5,))[0],
                       max_new_tokens=2))
    assert eng.stats.start_t is not None     # clock armed by submit
    eng.run()
    assert eng.stats.decode_tokens == 2
    eng.stats.reset()                        # benchmark warmup path
    assert eng.stats.start_t is None
    assert eng.stats.decode_tokens == 0 and eng.stats.prefill_tokens == 0
    assert eng.stats.steps == 0
