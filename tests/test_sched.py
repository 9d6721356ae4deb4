"""The scheduling API: policy semantics, per-shape plan caching, and the
engine's per-(bucket, batch) online planning (the behavior the old engine
docstring promised and never had)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import DepClusterConfig
from repro.core import PAPER_A6000, FinDEPPlanner
from repro.core.planner import PlannerConfig
from repro.core.solver import Plan
from repro.runtime import Request, RequestState, ServingEngine
from repro.sched import (EPSPipelinePolicy, FinDEPPolicy, OccupancySummary,
                         POLICIES, PlanCache, SchedulePolicy,
                         SequentialDEPPolicy, StaticPolicy, bucket_length,
                         make_policy)

CFG = get_smoke_config("qwen2-moe-a2.7b")
CLUSTER = DepClusterConfig(num_devices=8, ag=3, eg=5)


def mk_planner(**kw):
    pc = PlannerConfig(mem_cap_samples=8, **kw)
    return FinDEPPlanner(CFG, CLUSTER, PAPER_A6000, pc)


# ---------------------------------------------------------------------------
# policies
# ---------------------------------------------------------------------------

def test_policies_satisfy_protocol():
    planner = mk_planner()
    for name in POLICIES:
        pol = make_policy(name, planner, static_seq_len=256)
        assert isinstance(pol, SchedulePolicy)
        plan = pol.resolve("prefill", 256, 4)
        assert isinstance(plan, Plan)
        assert plan.r2 >= 1 and plan.m_a >= 1 and plan.r1 >= 1


def test_findep_forced_r2_1_matches_sequential():
    """FinDEP constrained to r2 = 1 IS the sequential coarse schedule:
    identical makespan and configuration under the same objective."""
    planner = mk_planner()
    seq = SequentialDEPPolicy(planner)
    for S, b in ((512, 4), (2048, 4), (2048, None)):
        p_seq = seq.resolve("prefill", S, b)
        p_fd = planner.plan(S, b, r2_cap=1)
        assert p_seq.r2 == 1
        assert p_fd.makespan == pytest.approx(p_seq.makespan)
        assert (p_fd.m_a, p_fd.r1, p_fd.order) == (
            p_seq.m_a, p_seq.r1, p_seq.order)


def test_findep_never_below_fixed_schedules():
    """Per-shape solving dominates both fixed-granularity baselines under
    the shared simulator objective."""
    planner = mk_planner()
    fd = FinDEPPolicy(planner)
    seq = SequentialDEPPolicy(planner)
    eps = EPSPipelinePolicy(planner, granularity=4)
    for S in (512, 2048):
        t_fd = fd.resolve("prefill", S, 4).throughput
        assert t_fd >= seq.resolve("prefill", S, 4).throughput * (1 - 1e-9)
        assert t_fd >= eps.resolve("prefill", S, 4).throughput * (1 - 1e-9)


def test_static_policy_is_shape_blind():
    planner = mk_planner()
    pol = StaticPolicy.from_planner(planner, 256)
    plans = {pol.resolve(ph, S, b)
             for ph in ("prefill", "decode")
             for S in (64, 256, 4096) for b in (1, 4, None)}
    assert len(plans) == 1


def test_eps_policy_fixed_granularity():
    planner = mk_planner()
    pol = EPSPipelinePolicy(planner, granularity=4)
    p = pol.resolve("prefill", 2048, 4)
    assert p.r1 == 1 and p.r2 == 4 and p.order == "AASS"


def test_infeasible_batch_falls_back_to_throughput_mode():
    """A live-batch larger than the memory cap must not crash the policy —
    it falls back to the solver-chosen batch."""
    planner = mk_planner()
    plan = FinDEPPolicy(planner).resolve("decode", 256, 1000)
    assert isinstance(plan, Plan)


def test_make_policy_rejects_unknown_and_bare_static():
    planner = mk_planner()
    with pytest.raises(ValueError):
        make_policy("nope", planner)
    with pytest.raises(ValueError):
        make_policy("static", planner)


# ---------------------------------------------------------------------------
# occupancy-aware resolution
# ---------------------------------------------------------------------------

def test_occupancy_summary_shape():
    occ = OccupancySummary.from_lengths([10, 70, 70, 500], max_bucket=256)
    assert occ.live == 4
    assert occ.hist == ((64, 1), (128, 2), (256, 1))
    # weighted mean (64 + 2*128 + 256) / 4 = 160 -> bucket 256
    assert occ.seq_bucket == bucket_length(160) == 256
    assert occ.max_bucket == 256
    # hashable + ordered: usable as a PlanCache key and sortable
    assert occ == OccupancySummary.from_lengths([70, 500, 10, 70],
                                                max_bucket=256)
    assert sorted([occ, OccupancySummary.from_lengths([5])])[0].live == 1


def test_policies_resolve_on_occupancy():
    """A decode resolve on an occupancy summary solves under the DECODE
    cost model (one token per live slot, attention linear in the
    histogram's mean context) — not the old prefill-style
    (seq_bucket, live) projection, which modeled a full sequence per slot
    and over-predicted a decode step's makespan by orders of magnitude."""
    planner = mk_planner()
    occ = OccupancySummary.from_lengths([100, 100, 400, 400])
    by_occ = FinDEPPolicy(planner).resolve("decode", occupancy=occ)
    assert by_occ == planner.plan_for_occupancy(occ)
    seq = SequentialDEPPolicy(planner).resolve("decode", occupancy=occ)
    assert seq == planner.plan_for_occupancy(occ, r2_cap=1)
    assert seq.r2 == 1
    # the decode-step makespan is far below the prefill-style projection
    proj = planner.plan(occ.seq_bucket, occ.live)
    assert by_occ.makespan < proj.makespan
    # EPS has no online solve; it still projects onto (seq_bucket, live)
    eps = EPSPipelinePolicy(planner, granularity=4)
    assert eps.resolve("decode", occupancy=occ) == \
        eps.resolve("decode", occ.seq_bucket, occ.live)
    # explicit shape arguments win over the summary
    p = FinDEPPolicy(planner).resolve("decode", 2048, occupancy=occ)
    assert p == FinDEPPolicy(planner).resolve("decode", 2048, occ.live)


def test_plan_cache_occupancy_keys():
    planner = mk_planner()
    cache = PlanCache(FinDEPPolicy(planner))
    occ_a = OccupancySummary.from_lengths([100, 100])
    occ_b = OccupancySummary.from_lengths([100, 2000])
    p1 = cache.get("decode", occupancy=occ_a)
    p2 = cache.get("decode", occupancy=occ_a)        # hit: same composition
    assert p1 is p2
    cache.get("decode", occupancy=occ_b)             # miss: new composition
    assert cache.stats.hits == 1 and cache.stats.misses == 2
    assert ("decode", occ_a) in cache.entries()
    with pytest.raises(ValueError):
        cache.get("decode")                           # neither shape nor occ


# ---------------------------------------------------------------------------
# PlanCache
# ---------------------------------------------------------------------------

class CountingPolicy:
    name = "counting"

    def __init__(self):
        self.calls = []

    def resolve(self, phase, seq_bucket, batch_per_device=None, *,
                occupancy=None, skew=None):
        self.calls.append((phase, seq_bucket, batch_per_device))
        return Plan(m_a=1, r1=1, m_e=1.0, r2=len(self.calls), order="AASS",
                    throughput=1.0, makespan=1.0)


def test_plan_cache_hit_miss_accounting():
    pol = CountingPolicy()
    cache = PlanCache(pol)
    p1 = cache.get("decode", 256, 4)
    p2 = cache.get("decode", 256, 4)          # hit: same shape
    assert p1 is p2
    assert cache.stats.hits == 1 and cache.stats.misses == 1
    assert len(pol.calls) == 1

    p3 = cache.get("decode", 256, 3)          # miss: batch changed
    p4 = cache.get("prefill", 256, 4)         # miss: phase changed
    p5 = cache.get("decode", 512, 4)          # miss: bucket changed
    assert len({p1.r2, p3.r2, p4.r2, p5.r2}) == 4
    assert cache.stats.misses == 4 and cache.stats.hits == 1
    assert cache.stats.solve_time_total >= 0.0
    assert len(cache) == 4
    assert cache.stats.hit_rate == pytest.approx(0.2)

    cache.clear()
    assert len(cache) == 0 and cache.stats.lookups == 0
    cache.get("decode", 256, 4)               # re-solve after clear
    assert len(pol.calls) == 5


def test_plan_cache_reresolves_on_shape_change_only():
    planner = mk_planner()
    cache = PlanCache(FinDEPPolicy(planner))
    for _ in range(10):
        cache.get("decode", 256, 4)
    assert planner.solve_count == 1
    cache.get("decode", 256, 2)
    assert planner.solve_count == 2


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

def _mk_requests(rng, n, lo, hi, max_new=3):
    return [Request(prompt=list(rng.randint(0, CFG.vocab_size,
                                            size=rng.randint(lo, hi))),
                    max_new_tokens=max_new) for _ in range(n)]


def test_engine_resolves_plan_per_prefill_bucket_and_decode_shape():
    """Acceptance: two different request-length mixes must produce >= 2
    distinct plans — the engine consults the policy per shape instead of
    freezing one plan at construction time. Decode plans are keyed by the
    KV ledger's OccupancySummary (the real composition), not the old
    (max_context, live-count) proxy."""
    eng = ServingEngine(CFG, num_slots=2, max_context=256,
                        plan_policy=FinDEPPolicy(mk_planner()),
                        dtype=jnp.float32)
    rng = np.random.RandomState(0)
    # mix 1: short prompts (bucket 64); mix 2: long prompts (bucket 256)
    for r in _mk_requests(rng, 2, 4, 9, max_new=8) + \
            _mk_requests(rng, 2, 150, 200, max_new=8):
        eng.submit(r)
    finished = eng.run()
    assert len(finished) == 4
    keys = eng.resolved_plans().keys()
    prefill_buckets = {k[1] for k in keys if k[0] == "prefill"}
    assert len(prefill_buckets) >= 2, keys
    assert len(eng.plan_cache.distinct_plans()) >= 2
    decode_keys = [k for k in keys if k[0] == "decode"]
    assert len(decode_keys) >= 2, keys            # churn => >= 2 occupancies
    assert all(isinstance(k[1], OccupancySummary) for k in decode_keys)
    # steady-state decode must be served from the cache, not the solver
    assert eng.plan_cache.stats.hits > eng.plan_cache.stats.misses


def test_static_policy_reproduces_unscheduled_engine_bitforbit():
    """Plan threading must not perturb numerics: a StaticPolicy engine
    produces exactly the tokens of an engine with no policy at all."""
    rng = np.random.RandomState(1)
    prompts = [list(rng.randint(0, CFG.vocab_size, size=n))
               for n in (5, 9, 13)]

    def serve(policy):
        eng = ServingEngine(CFG, num_slots=2, max_context=128,
                            plan_policy=policy, dtype=jnp.float32, seed=0)
        reqs = [Request(prompt=p, max_new_tokens=4) for p in prompts]
        for r in reqs:
            eng.submit(r)
        eng.run()
        return [r.output for r in reqs]

    static = StaticPolicy.from_planner(mk_planner(), 128)
    assert serve(None) == serve(static)


@pytest.mark.parametrize("name", POLICIES)
def test_all_policies_serve_end_to_end(name):
    pol = make_policy(name, mk_planner(), static_seq_len=64)
    eng = ServingEngine(CFG, num_slots=2, max_context=64,
                        plan_policy=pol, dtype=jnp.float32)
    rng = np.random.RandomState(2)
    reqs = _mk_requests(rng, 3, 4, 10, max_new=2)
    for r in reqs:
        eng.submit(r)
    finished = eng.run()
    assert len(finished) == 3
    assert all(r.state == RequestState.FINISHED for r in reqs)
    assert len(eng.plan_cache) >= 1


def test_execution_context_plan_shim_is_gone():
    """PR 1's ``ExecutionContext(plan=)`` shim is removed: plans flow per
    call only (model.forward/prefill/decode_step(plan=...))."""
    from repro.models.transformer import ExecutionContext
    with pytest.raises(TypeError):
        ExecutionContext(plan=Plan(m_a=1, r1=1, m_e=1.0, r2=2,
                                   order="AASS", throughput=0,
                                   makespan=0))
    assert not hasattr(ExecutionContext(), "plan")
