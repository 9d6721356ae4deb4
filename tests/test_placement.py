"""Expert placement subsystem: load telemetry, hot-expert replication,
skew-aware planning (ROADMAP item 2).

Pure-python pieces (tracker EWMA, greedy rebalancer, skew summaries,
plan-cache keys, REP lowering) run in-process; the replicated DEP
executor's bit-parity and drop accounting run under a 4-device subprocess
mesh like tests/test_dep_distributed.py.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.perf_model import (PAPER_A6000, DepClusterConfig,
                                   DepModelSpec, build_stage_models)
from repro.core.planner import FinDEPPlanner, PlannerConfig
from repro.core.taskgraph import (EXP, GATE, REP, LoweringSpec, lower,
                                  lower_exec)
from repro.placement import (UNIFORM_SKEW, ExpertLoadTracker, Placement,
                             SkewSummary, capacity_scale, max_rank_load,
                             modeled_exp_time, rank_loads, rebalance,
                             zipf_loads)
from repro.sched import PlanCache
from repro.sched.policy import FinDEPPolicy

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def _planner(**kw):
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    return FinDEPPlanner(cfg, DepClusterConfig(8, 3, 5), PAPER_A6000,
                         PlannerConfig(mem_cap_samples=8, **kw))


# ---------------------------------------------------------------------------
# tracker: EWMA math + zipf loads
# ---------------------------------------------------------------------------

def test_zipf_loads_shape_and_skew():
    f = zipf_loads(16, s=1.2)
    assert f.shape == (16,)
    assert abs(f.sum() - 1.0) < 1e-12
    assert f[0] == f.max() and f[-1] == f.min()
    perm = list(reversed(range(16)))
    g = zipf_loads(16, s=1.2, permutation=perm)
    assert g[perm[0]] == f[0]


def test_tracker_ewma_matches_hand_rolled():
    tr = ExpertLoadTracker(4, smoothing=0.25)
    h1 = np.array([8.0, 4.0, 2.0, 2.0])
    h2 = np.array([1.0, 1.0, 1.0, 1.0])
    tr.observe(h1)
    np.testing.assert_allclose(tr.layer_loads(0), h1 / h1.sum())
    tr.observe(h2)
    want = 0.25 * (h2 / h2.sum()) + 0.75 * (h1 / h1.sum())
    np.testing.assert_allclose(tr.layer_loads(0), want)
    # [L, E] observations track per layer; aggregate() is the layer mean
    tr2 = ExpertLoadTracker(4)
    tr2.observe(np.stack([h1, h2]))
    assert tr2.layers == 2
    np.testing.assert_allclose(
        tr2.aggregate(), (h1 / h1.sum() + h2 / h2.sum()) / 2)
    # normalization: prefill (many tokens) and decode (few) weigh equally
    tr3 = ExpertLoadTracker(4, smoothing=0.5)
    tr3.observe(h1 * 100)
    tr3.observe(h1)
    np.testing.assert_allclose(tr3.layer_loads(0), h1 / h1.sum())


def test_tracker_imbalance_and_reset():
    tr = ExpertLoadTracker(4)
    assert tr.imbalance() == pytest.approx(1.0)   # uniform before data
    tr.observe([10.0, 0.0, 0.0, 0.0])
    assert tr.imbalance() == pytest.approx(4.0)   # one expert owns all
    tr.reset()
    assert tr.observations == 0 and tr.layers == 0


def test_tracker_rejects_bad_shapes():
    tr = ExpertLoadTracker(4)
    with pytest.raises(ValueError):
        tr.observe(np.zeros(5))
    with pytest.raises(ValueError):
        ExpertLoadTracker(4, smoothing=0.0)


# ---------------------------------------------------------------------------
# rebalancer: greedy LPT + hot replication
# ---------------------------------------------------------------------------

def test_rebalance_reduces_modeled_exp_time():
    loads = zipf_loads(16, s=1.2)
    uniform = Placement.uniform(16, 4)
    t_uniform = modeled_exp_time(uniform, loads, 1.0)
    lpt = rebalance(loads, 4)
    t_lpt = modeled_exp_time(lpt, loads, 1.0)
    hot = rebalance(loads, 4, replicate_hot_k=2, epoch=1)
    t_hot = modeled_exp_time(hot, loads, 1.0)
    # zipf's hot head lands in rank 0's contiguous block: LPT flattens
    # it, replication removes it from the EG lane entirely
    assert t_lpt < t_uniform
    assert t_hot < t_lpt
    assert hot.replicated == (0, 1)               # the two hottest ids
    assert hot.epoch == 1 and hot.hot_experts == 2


def test_rebalance_keeps_uniform_slot_counts():
    loads = zipf_loads(12, s=1.5)
    pl = rebalance(loads, 3, replicate_hot_k=2)
    counts = [0] * 3
    for r in pl.assignment:
        counts[r] += 1
    assert counts == [4, 4, 4]
    # perm is a true permutation realizing the assignment
    perm = pl.perm
    assert sorted(perm) == list(range(12))
    per = pl.experts_per_rank
    for e, r in enumerate(pl.assignment):
        assert perm[e] // per == r
    # deterministic: same inputs, same placement
    assert rebalance(loads, 3, replicate_hot_k=2) == pl


def test_rebalance_flat_loads_is_noop_quality():
    loads = np.ones(8) / 8
    pl = rebalance(loads, 4)
    assert max_rank_load(pl, loads) == pytest.approx(0.25)
    assert pl.hot_experts == 0
    np.testing.assert_allclose(rank_loads(pl, loads), 0.25)


def test_placement_uniform_identity():
    pl = Placement.uniform(8, 4)
    assert pl.is_uniform
    assert pl.perm == tuple(range(8))
    lpt = rebalance(zipf_loads(8, 1.2), 4)
    assert not lpt.is_uniform


def test_placement_validation():
    with pytest.raises(ValueError):
        Placement(num_experts=4, num_ranks=2, assignment=(0, 0, 0, 1))
    with pytest.raises(ValueError):
        Placement(num_experts=4, num_ranks=3, assignment=(0, 1, 2, 0))
    with pytest.raises(ValueError):
        Placement(num_experts=4, num_ranks=2, assignment=(0, 0, 1, 1),
                  replicated=(1, 1))


# ---------------------------------------------------------------------------
# skew summary + capacity scale
# ---------------------------------------------------------------------------

def test_skew_summary_quantized_and_hashable():
    tr = ExpertLoadTracker(8)
    tr.observe(zipf_loads(8, 1.2))
    s1 = tr.summary(num_ranks=2)
    tr.observe(zipf_loads(8, 1.2))   # same regime -> same fingerprint
    s2 = tr.summary(num_ranks=2)
    assert s1 == s2 and hash(s1) == hash(s2)
    assert s1.kappa % 0.125 == 0 and s1.max_expert % 0.125 == 0
    assert not s1.is_uniform
    assert UNIFORM_SKEW.is_uniform
    # no observations: uniform fingerprint carrying the placement epoch
    empty = ExpertLoadTracker(8).summary(
        placement=rebalance(zipf_loads(8), 2, replicate_hot_k=1, epoch=3))
    assert empty.epoch == 3 and empty.hot_k == 1


def test_skew_summary_replication_semantics():
    tr = ExpertLoadTracker(8)
    tr.observe(zipf_loads(8, 1.2))
    hot = rebalance(tr.aggregate(), 2, replicate_hot_k=2, epoch=1)
    s = tr.summary(placement=hot)
    # replicated experts carry their tokens off the EG lane
    assert s.rho > 0.0
    assert s.kappa < tr.summary(num_ranks=2).kappa
    assert s.hot_k == 2 and s.epoch == 1


def test_capacity_scale():
    assert capacity_scale(None, 1.25) == 1.0
    assert capacity_scale(UNIFORM_SKEW, 1.25) == 1.0
    hot = SkewSummary(max_expert=2.5)
    assert capacity_scale(hot, 1.25) == pytest.approx(2.0)
    assert capacity_scale(hot, 4.0) == 1.0        # headroom already covers


# ---------------------------------------------------------------------------
# replica-aware lowering (REP tasks) + placement epoch identity
# ---------------------------------------------------------------------------

def test_lowering_zero_replicas_is_structurally_legacy():
    spec = LoweringSpec(T=2)
    base = lower_exec(2, "ASAS")
    assert base.hot_experts == 0 and base.placement_epoch == 0
    assert not base.tasks_of(REP)
    from repro.core.solver import Plan
    plan = Plan(m_a=1, r1=1, m_e=1, r2=2, order="ASAS",
                throughput=0, makespan=0)
    assert plan.exec_graph() is lower_exec(2, "ASAS")   # cached identity
    g0 = lower(plan, spec)
    g1 = lower(plan, spec, hot_experts=0, placement_epoch=0)
    assert g0 is g1


def test_lowering_rep_tasks_depend_on_gate():
    g = lower_exec(2, "ASAS", hot_experts=1, placement_epoch=5)
    all_tasks = g.tasks
    reps = g.tasks_of(REP)
    assert reps, "hot_experts > 0 must emit REP tasks"
    for _, t in reps:
        deps = [all_tasks[d].kind for d in t.deps]
        assert GATE in deps
    assert g.hot_experts == 1 and g.placement_epoch == 5
    # epoch changes identity (fresh jit key) but not structure
    g2 = lower_exec(2, "ASAS", hot_experts=1, placement_epoch=6)
    assert g2 is not g and g2 != g
    assert len(g2.tasks) == len(g.tasks)
    assert [t.kind for t in g2.tasks] == [t.kind for t in g.tasks]
    # executor walk: REP runs after its gate
    kinds = [t.kind for t in g.exec_walk()]
    assert REP in kinds
    assert kinds.index(REP) > kinds.index(GATE)


def test_exec_graph_placement_epoch_keys():
    from repro.core.solver import Plan
    plan = Plan(m_a=1, r1=1, m_e=1, r2=2, order="AASS",
                throughput=0, makespan=0)
    a = plan.exec_graph(hot_experts=1, placement_epoch=1)
    b = plan.exec_graph(hot_experts=1, placement_epoch=2)
    c = plan.exec_graph()
    assert a != b and a != c
    assert hash(a) != hash(c)


# ---------------------------------------------------------------------------
# skew-aware planning: cost model + plan-cache keys + invalidation
# ---------------------------------------------------------------------------

def test_stage_models_uniform_skew_is_legacy():
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    spec = DepModelSpec.from_model_config(cfg, 512)
    cluster = DepClusterConfig(8, 3, 5)
    legacy = build_stage_models(PAPER_A6000, spec, cluster)
    uni = build_stage_models(PAPER_A6000, spec, cluster, skew=UNIFORM_SKEW)
    assert uni.t_e == legacy.t_e and uni.t_c == legacy.t_c
    assert uni.t_rep is None
    skewed = build_stage_models(
        PAPER_A6000, spec, cluster,
        skew=SkewSummary(kappa=1.5, rho=0.25, max_expert=2.0, hot_k=1,
                         epoch=1))
    # worst-rank EXP inflates; comm deflates by the hot fraction
    assert skewed.t_e.beta == pytest.approx(legacy.t_e.beta * 1.5)
    assert skewed.t_c.beta == pytest.approx(legacy.t_c.beta * 0.75)
    assert skewed.t_rep is not None and skewed.t_rep.beta > 0


def test_planner_memoizes_per_skew():
    plr = _planner()
    p_uni = plr.plan(512, 8)
    assert plr.plan(512, 8, skew=UNIFORM_SKEW) is p_uni
    skew = SkewSummary(kappa=1.5, rho=0.25, max_expert=2.0, hot_k=1,
                       epoch=1)
    p_skew = plr.plan(512, 8, skew=skew)
    n = plr.solve_count
    assert plr.plan(512, 8, skew=skew) is p_skew
    assert plr.solve_count == n


def test_replication_flattens_zipf_skew_on_deepseek_v2_lite():
    """A noisy Zipf(1.2) routing trace over deepseek-v2-lite's 64 experts
    on 4 EP ranks: LPT re-placement flattens the worst rank, hot-expert
    replication flattens it further, and the skew-aware planner's modeled
    makespan with replication is at least 10% below the uniform layout's."""
    from repro.configs import get_config
    cfg = get_config("deepseek-v2-lite")
    E, ranks = cfg.moe.num_experts, 4
    rng = np.random.RandomState(0)
    probs = zipf_loads(E, s=1.2)
    tracker = ExpertLoadTracker(E)
    for _ in range(32):
        tracker.observe(rng.multinomial(4096, probs))
    loads = tracker.aggregate()
    layouts = {
        "uniform": Placement.uniform(E, ranks),
        "lpt": rebalance(loads, ranks),
        "replicated": rebalance(loads, ranks, replicate_hot_k=4, epoch=1),
    }
    imbalance = {name: max_rank_load(pl, loads) * ranks
                 for name, pl in layouts.items()}
    assert (imbalance["replicated"] < imbalance["lpt"]
            < imbalance["uniform"]), imbalance
    planner = FinDEPPlanner(
        cfg, DepClusterConfig(num_devices=2 * ranks, ag=ranks, eg=ranks),
        PAPER_A6000,
        PlannerConfig(mem_cap_samples=4, r1_cap=4, r2_cap=32, T_override=8))
    makespan = {name: planner.plan(2048, 4, skew=tracker.summary(
        placement=layouts[name])).makespan
        for name in ("uniform", "replicated")}
    assert makespan["replicated"] <= 0.9 * makespan["uniform"], makespan


def test_plan_cache_skew_keys_and_epoch_invalidation():
    cache = PlanCache(FinDEPPolicy(_planner()))
    p0 = cache.get("prefill", 512, 8)
    s1 = SkewSummary(kappa=1.5, rho=0.25, max_expert=2.0, hot_k=1, epoch=1)
    p1 = cache.get("prefill", 512, 8, skew=s1)
    assert ("prefill", 512, 8) in cache.entries()
    assert ("prefill", 512, 8, s1) in cache.entries()
    # uniform skew normalizes to the legacy key (no duplicate entry)
    assert cache.get("prefill", 512, 8, skew=UNIFORM_SKEW) is p0
    assert len(cache) == 2
    # refresh parses the skew-suffixed key back apart
    cache.refresh(("prefill", 512, 8, s1))
    assert cache.stats.refreshes == 1
    # an epoch bump keys NEW entries; the engine invalidates stale ones
    s2 = SkewSummary(kappa=1.0, rho=0.25, max_expert=2.0, hot_k=1, epoch=2)
    cache.get("prefill", 512, 8, skew=s2)
    for key in list(cache.entries()):
        tail = key[-1]
        if isinstance(tail, SkewSummary) and tail.epoch != 2:
            cache.invalidate(key)
    assert ("prefill", 512, 8, s1) not in cache.entries()
    assert ("prefill", 512, 8, s2) in cache.entries()
    assert p1 is not None


# ---------------------------------------------------------------------------
# dropped-token accounting (single device)
# ---------------------------------------------------------------------------

def test_moe_dispatch_counts_dropped_tokens():
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.models import moe as moe_lib
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    key = jax.random.PRNGKey(0)
    params = moe_lib.moe_init(key, cfg.d_model, cfg.moe, 4)
    x = jax.random.normal(key, (6, 8, cfg.d_model), jnp.float32)
    y, aux, stats = moe_lib.moe_apply_capacity(params, x, cfg.moe, 4,
                                               return_stats=True)
    assert stats.load.shape == (4,)
    # every assignment is either kept or dropped
    total = 6 * 8 * cfg.moe.top_k
    assert float(stats.load.sum()) == pytest.approx(total)
    assert 0 <= int(stats.dropped) <= total
    # ample capacity drops nothing
    import dataclasses
    roomy = dataclasses.replace(cfg.moe, capacity_factor=16.0)
    _, _, st2 = moe_lib.moe_apply_capacity(params, x, roomy, 4,
                                           return_stats=True)
    assert int(st2.dropped) == 0
    # the default return stays the legacy 2-tuple, bit-identical
    y2, aux2 = moe_lib.moe_apply_capacity(params, x, cfg.moe, 4)
    assert bool(jnp.array_equal(y, y2)) and bool(jnp.array_equal(aux, aux2))


def test_expert_capacity_scale():
    from repro.configs import get_smoke_config
    from repro.models import moe as moe_lib
    mcfg = get_smoke_config("qwen2-moe-a2.7b").moe
    base = moe_lib.expert_capacity(64, mcfg, 4)
    assert moe_lib.expert_capacity(64, mcfg, 4, scale=1.0) == base
    assert moe_lib.expert_capacity(64, mcfg, 4, scale=2.0) == 2 * base
    # scale < 1 never shrinks below the configured sizing
    assert moe_lib.expert_capacity(64, mcfg, 4, scale=0.5) == base


# ---------------------------------------------------------------------------
# replicated DEP executor: bit-parity + drop regression (subprocess mesh)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_replicated_executor_bit_parity_and_drops():
    out = run_sub(textwrap.dedent("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models import moe as moe_lib
        from repro.models.transformer import ExecutionContext
        from repro.core import dep
        from repro.core.solver import Plan
        from repro.placement import Placement, rebalance
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,2), ("data","model"))
        cfg = get_smoke_config("qwen2-moe-a2.7b")
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=8.0))
        key = jax.random.PRNGKey(1)
        params = moe_lib.moe_init(key, cfg.d_model, cfg.moe, 4)
        x = jax.random.normal(key, (4, 8, cfg.d_model), jnp.float32)
        ctx = ExecutionContext(mesh=mesh, moe_impl="dep")
        plan = Plan(m_a=1, r1=1, m_e=1, r2=2, order="ASAS",
                    throughput=0, makespan=0)
        with mesh:
            y_ref, _ = jax.jit(lambda p, x: dep.moe_apply_dep(
                p, x, cfg.moe, ctx, 4, plan=plan.exec_graph()))(params, x)

        # uniform placement takes the legacy path bit-identically
        uni = Placement.uniform(4, 2)
        with mesh:
            y_uni, _ = jax.jit(lambda p, x: dep.moe_apply_dep(
                p, x, cfg.moe, ctx, 4, plan=plan.exec_graph(),
                placement=uni))(params, x)
        assert bool(jnp.array_equal(y_ref, y_uni)), "uniform placement"
        print("ok uniform")

        # replicated placement on engine-permuted weights: bit-identical
        # to the unreplicated walk (each hot row's FFN is the same einsum
        # rows either way)
        pl = rebalance([8.0, 1.0, 1.0, 1.0], 2, replicate_hot_k=1, epoch=1)
        assert pl.hot_experts == 1
        gather = jnp.asarray(np.argsort(np.asarray(pl.perm)))
        pp = dict(params)
        pp["experts"] = jax.tree.map(lambda a: a[gather], params["experts"])
        g = plan.exec_graph(hot_experts=1, placement_epoch=pl.epoch)
        with mesh:
            y_rep, _, st_rep = jax.jit(lambda p, x: dep.moe_apply_dep(
                p, x, cfg.moe, ctx, 4, plan=g, placement=pl,
                return_stats=True))(pp, x)
        assert bool(jnp.array_equal(y_ref, y_rep)), float(
            jnp.max(jnp.abs(y_ref - y_rep)))
        print("ok replicated")

        # drop regression at TIGHT equal capacity: the replicated walk
        # never drops more than the unreplicated one (hot tokens bypass
        # the capacity-bound dispatch buffers)
        tight = dataclasses.replace(cfg.moe, capacity_factor=1.0)
        with mesh:
            _, _, st_base = jax.jit(lambda p, x: dep.moe_apply_dep(
                p, x, tight, ctx, 4, plan=plan.exec_graph(),
                return_stats=True))(params, x)
            _, _, st_hot = jax.jit(lambda p, x: dep.moe_apply_dep(
                p, x, tight, ctx, 4, plan=g, placement=pl,
                return_stats=True))(pp, x)
        base_d, hot_d = int(st_base.dropped), int(st_hot.dropped)
        assert hot_d <= base_d, (hot_d, base_d)
        # stats stay logical: load histograms agree independent of layout
        assert bool(jnp.array_equal(st_base.load, st_hot.load))
        print("ok drops", base_d, hot_d)
    """))
    assert "ok uniform" in out and "ok replicated" in out \
        and "ok drops" in out


@pytest.mark.slow
def test_engine_rebalance_end_to_end():
    out = run_sub(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.core.perf_model import PAPER_A6000, DepClusterConfig
        from repro.core.planner import FinDEPPlanner, PlannerConfig
        from repro.placement import SkewSummary
        from repro.runtime.engine import ServingEngine
        from repro.runtime.request import Request
        from repro.sched import FinDEPPolicy
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,2), ("data","model"))
        cfg = get_smoke_config("qwen2-moe-a2.7b")
        def make(**kw):
            plr = FinDEPPlanner(cfg, DepClusterConfig(4, 2, 2),
                                PAPER_A6000,
                                PlannerConfig(mem_cap_samples=8))
            return ServingEngine(cfg, num_slots=4, max_context=64,
                                 seed=0, mesh=mesh,
                                 plan_policy=FinDEPPolicy(plr), **kw)
        def serve(eng, n=3, new=4):
            for i in range(n):
                eng.submit(Request(prompt=list(range(2, 10 + i)),
                                   max_new_tokens=new))
            done = eng.run()
            return sorted([tuple(r.output) for r in done])

        # telemetry on (no placement yet) == telemetry off, bit-identical
        base = serve(make())
        tracked_eng = make(track_expert_load=True)
        tracked = serve(tracked_eng)
        assert base == tracked, (base, tracked)
        assert tracked_eng.load_tracker.observations > 0
        assert tracked_eng.stats.dropped_tokens >= 0
        tracked_eng.close()
        print("ok engine parity")

        # forced rebalance mid-serve: epoch bumps, replica executes,
        # stale-epoch cache entries are invalidated, serving continues
        eng = make(replicate_hot_k=1, rebalance_threshold=10.0)
        serve(eng, n=2, new=3)
        pl = eng.rebalance_now()
        assert pl is not None and pl.hot_experts == 1 and pl.epoch >= 1
        serve(eng, n=2, new=3)
        for key in eng.resolved_plans():
            tail = key[-1]
            if isinstance(tail, SkewSummary):
                assert tail.epoch == pl.epoch, key
        assert eng.expert_load()["hot_experts"] == 1.0
        eng.close()
        print("ok engine rebalance")
    """))
    assert "ok engine parity" in out and "ok engine rebalance" in out


# ---------------------------------------------------------------------------
# engine weight permutation (no mesh needed)
# ---------------------------------------------------------------------------

def test_apply_placement_permutes_weights_and_composes():
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.runtime.engine import ServingEngine
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    eng = ServingEngine(cfg, num_slots=2, max_context=32)
    moe_layers = [i for i, layer in enumerate(eng.params["layers"])
                  if "moe" in layer]
    orig = {i: jax.tree.map(jnp.copy,
                            eng.params["layers"][i]["moe"]["experts"])
            for i in moe_layers}

    def check(pl):
        for i in moe_layers:
            cur = eng.params["layers"][i]["moe"]["experts"]
            for name in ("gate", "up", "down"):
                for e in range(pl.num_experts):
                    want = orig[i][name][e]
                    got = cur[name][pl.perm[e]]
                    assert bool(jnp.array_equal(want, got)), (i, name, e)

    p1 = rebalance([8.0, 1.0, 2.0, 1.0], 2, replicate_hot_k=1, epoch=1)
    eng._apply_placement(p1)
    assert eng.placement is p1
    check(p1)
    # second epoch composes on top of the first permutation
    p2 = rebalance([1.0, 1.0, 1.0, 9.0], 2, replicate_hot_k=1, epoch=2)
    eng._apply_placement(p2)
    check(p2)
    eng.close()
