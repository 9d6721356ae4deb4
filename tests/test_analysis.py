"""The static verification layer (repro.analysis).

Positive direction: every lowering the repo actually produces passes all
three passes clean (the CI gate property), and the planner/engine
``validate=`` knobs accept real plans.

Negative direction (detector sensitivity): each pass must FLAG a
deliberately broken artifact with an actionable message — a corrupted
service order deadlocks, a tampered schedule races, a tampered block
table dereferences garbage, a mutable static arg / host sync / tracer
leak lints, and a tampered hint vector is rejected by
``ServingEngine(validate=True)`` at plan time.

Plus the jit-static hashability regression: every type the registry
declares jit-static must hash/compare by value across construction
paths (fresh solves, cached-property materialization, epoch bumps).
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import AnalysisError, PASSES, codes, run_all
from repro.analysis.graphcheck import (check_capacity, check_exec_program,
                                       check_graph, check_hints,
                                       check_schedule_result,
                                       check_structure, find_deadlock,
                                       sweep)
from repro.analysis.jitlint import (STATIC_ARG_TYPES, check_static_types,
                                    lint_source)
from repro.analysis.kernelcheck import (check_dense_index_map,
                                        check_flash_index_map,
                                        check_paged_index_map)
from repro.analysis.report import Violation
from repro.configs import get_smoke_config
from repro.configs.base import DepClusterConfig
from repro.core import PAPER_A6000, FinDEPPlanner
from repro.core.planner import PlannerConfig
from repro.core.solver import Plan
from repro.core.taskgraph import (ATTN, EXP, GATE, _HINT_COSTS, ExecProgram,
                                  lower_exec, schedule, stream_major_order,
                                  stream_serial_deps)
from repro.placement import Placement, SkewSummary
from repro.runtime import Request, ServingEngine
from repro.sched import StaticPolicy

CFG = get_smoke_config("qwen2-moe-a2.7b")
CLUSTER = DepClusterConfig(num_devices=8, ag=3, eg=5)


def mk_planner(**kw):
    return FinDEPPlanner(CFG, CLUSTER, PAPER_A6000,
                         PlannerConfig(mem_cap_samples=8), **kw)


class _TamperedGraph:
    """Duck-typed stand-in: a real graph's parameters with a corrupted
    task tuple (the real TaskGraph derives its tasks from the lowering
    parameters, so a broken tuple can only come from a future bug —
    which is exactly what the structural checks must catch)."""

    def __init__(self, graph, tasks):
        for f in ("T", "r1", "r2", "order", "m_e", "has_shared",
                  "shared_blocks_a2e", "hot_experts", "placement_epoch",
                  "shared_segments"):
            setattr(self, f, getattr(graph, f))
        self.tasks = tuple(tasks)


# ---------------------------------------------------------------------------
# graphcheck: positives
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("order", ["ASAS", "AASS"])
@pytest.mark.parametrize("r1", [1, 2, 4])
def test_exec_lowerings_clean(order, r1):
    g = lower_exec(4, order, m_e=3, r1=r1)
    assert check_graph(g) == []
    for mode in ("off", "streams"):
        assert check_exec_program(ExecProgram(g, mode, None)) == []


def test_planner_lowering_clean():
    planner = mk_planner()
    plan = planner.plan(256, 4)
    assert check_graph(planner.lower(plan)) == []
    assert check_exec_program(plan.exec_program()) == []


def test_fast_sweep_clean():
    """The CI gate property on the representative slice: every policy's
    lowering over the reduced shape space, zero violations."""
    violations, combos = sweep(fast=True)
    assert violations == []
    assert combos > 50


# ---------------------------------------------------------------------------
# graphcheck: negatives (detector sensitivity)
# ---------------------------------------------------------------------------

def test_structure_flags_forward_dep_and_bad_ranges():
    g = lower_exec(2, "ASAS")
    tasks = list(g.tasks)
    tasks[1] = dataclasses.replace(tasks[1], deps=(len(tasks) + 3,))
    tasks[2] = dataclasses.replace(tasks[2], layer=99)
    vs = check_structure(_TamperedGraph(g, tasks))
    assert set(codes(vs)) == {"dep-not-earlier", "layer-range"}
    msg = next(str(v) for v in vs if v.code == "dep-not-earlier")
    assert "not an earlier emission" in msg and "graphcheck" in msg


def test_capacity_flags_missing_chunk():
    g = lower_exec(3, "ASAS", r1=2)
    dropped = next(t for t in g.tasks if t.kind == EXP and t.chunk == 1)
    vs = check_capacity(_TamperedGraph(
        g, [t for t in g.tasks if t is not dropped]))
    assert "capacity-conservation" in codes(vs)
    msg = next(str(v) for v in vs if v.code == "capacity-conservation")
    assert "EXP" in msg and "missing" in msg


def test_race_detector_flags_tampered_schedule():
    g = lower_exec(4, "ASAS", r1=2)
    res = schedule(g, _HINT_COSTS)
    assert check_schedule_result(res) == []
    res.starts[len(g.tasks) - 1] = 0.0       # yank the last task to t=0
    vs = check_schedule_result(res)
    got = set(codes(vs))
    assert "lane-race" in got and "dep-order" in got
    msg = next(v.message for v in vs if v.code == "lane-race")
    assert "occupies the lane" in msg


def test_deadlock_flags_corrupted_service_order():
    """GATE served before its ATTN dep on the shared AG lane is an
    immediate two-cycle: GATE dep-waits ATTN, ATTN lane-waits GATE."""
    g = lower_exec(2, "ASAS", r1=2)
    order = list(range(len(g.tasks)))
    ai = next(i for i, t in enumerate(g.tasks) if t.kind == ATTN)
    gi = next(i for i, t in enumerate(g.tasks)
              if t.kind == GATE and t.mb == g.tasks[ai].mb)
    pa, pg = order.index(ai), order.index(gi)
    order[pa], order[pg] = gi, ai
    cycle = find_deadlock(g, service_order=order)
    assert cycle is not None
    kinds = {g.tasks[i].kind for i in cycle}
    assert kinds == {ATTN, GATE}


def test_deadlock_flags_truncated_service_order():
    g = lower_exec(2, "ASAS")
    stuck = find_deadlock(g, service_order=range(len(g.tasks) - 1))
    assert stuck == [len(g.tasks) - 1]


def test_executed_realizations_are_deadlock_free():
    """The realizations the system actually takes must complete — the
    emission order, and the sequential executor's stream-major order
    under the cross-stream serialization edges."""
    for r1 in (1, 2, 4):
        g = lower_exec(4, "ASAS", r1=r1)
        assert find_deadlock(g) is None
        assert find_deadlock(g, service_order=stream_major_order(g),
                             extra_deps=stream_serial_deps(g)) is None


def test_hint_checks_flag_tampered_vectors():
    g = lower_exec(4, "ASAS", r1=2)
    n = len(g.tasks)
    good = schedule(g, _HINT_COSTS).priority_hints()
    assert check_hints(ExecProgram(g, "streams", good)) == []

    reversed_ = ExecProgram(g, "streams", tuple(reversed(good)))
    assert codes(check_hints(reversed_)) == ["hint-dep-order"]
    short = ExecProgram(g, "streams", good[:-1])
    assert codes(check_hints(short)) == ["hint-length"]
    dup = ExecProgram(g, "streams", (0,) * n)
    assert "hint-not-permutation" in codes(check_hints(dup))
    assert codes(check_exec_program(reversed_)) == ["hint-dep-order"]


def test_exec_interleaved_error_names_both_tasks():
    """Satellite: the dep-consistency failure must name the offending
    pair (kind/layer/mb/chunk), their hint ranks and their interleaved
    positions — not just two bare indices."""
    g = lower_exec(4, "ASAS", r1=2)
    bad = tuple(reversed(schedule(g, _HINT_COSTS).priority_hints()))
    with pytest.raises(ValueError) as ei:
        g.exec_interleaved(bad)
    msg = str(ei.value)
    assert "would run before its dependency" in msg
    assert "(layer=" in msg and "mb=" in msg and "chunk=" in msg
    assert "hint" in msg and "interleaved position" in msg


# ---------------------------------------------------------------------------
# kernelcheck
# ---------------------------------------------------------------------------

def test_production_index_maps_clean():
    assert check_dense_index_map(40, 16, [0, 1, 7, 8, 9, 39, 40]) == []
    assert check_flash_index_map(2, 8, 2, 4, 4) == []


def test_paged_checker_flags_tampered_table():
    bs = 16
    # row 0: block 1 is in-length but unallocated; row 1: page out of
    # range; row 2: an in-length block mapped to the scratch page
    tables = np.array([[3, -1, -1], [99, 2, -1], [0, 4, -1]], np.int32)
    vs = check_paged_index_map(tables, [2 * bs, bs, bs], num_pages=8,
                               bs=bs)
    got = set(codes(vs))
    assert {"paged-live-step-unallocated", "paged-page-range",
            "paged-live-step-scratch"} <= got
    msg = next(v.message for v in vs
               if v.code == "paged-live-step-unallocated")
    assert "promised coverage" in msg


def test_paged_checker_accepts_real_ledger():
    from repro.runtime.paging import PagedKVCacheManager
    bs = 16
    kv = PagedKVCacheManager(3, max_context=4 * bs, block_size=bs,
                             num_blocks=16)
    kv.take(0)
    kv.assign_blocks(0, list(range(bs + 3)))
    kv.set_length(0, bs + 4)
    assert check_paged_index_map(kv._tables, kv.lengths(),
                                 kv.pool.num_blocks, bs) == []


# ---------------------------------------------------------------------------
# jitlint
# ---------------------------------------------------------------------------

def test_jitlint_repo_clean():
    violations, _ = __import__("repro.analysis.jitlint",
                               fromlist=["run"]).run()
    assert violations == []


def test_jitlint_flags_mutable_static_and_host_sync():
    src = (
        "import functools\n"
        "import jax\n"
        "import numpy as np\n"
        "\n"
        "@functools.partial(jax.jit, static_argnames=('opts',))\n"
        "def step(x, opts=[]):\n"
        "    y = np.asarray(x)\n"
        "    return y.item()\n"
    )
    vs = lint_source(src, "fake.py", hot=True)
    got = codes(vs)
    assert "static-arg-mutable" in got
    assert got.count("host-sync") == 2
    msg = next(v.message for v in vs if v.code == "static-arg-mutable")
    assert "opts" in msg


def test_jitlint_flags_tracer_context_leak():
    src = (
        "def walk():\n"
        "    from repro.obs.trace import active_tracer\n"
        "    return active_tracer()\n"
    )
    vs = lint_source(src, "dep.py", tracer_module=True)
    assert "tracer-context-leak" in codes(vs)


def test_static_type_registry_clean():
    assert check_static_types() == []
    assert len(STATIC_ARG_TYPES) >= 5


def test_static_type_checker_flags_unhashable_fields():
    @dataclasses.dataclass(frozen=True)
    class BadStatic:
        xs: list

    @dataclasses.dataclass
    class NotFrozen:
        x: int = 0

    vs = check_static_types(extra=(BadStatic, NotFrozen))
    msgs = " | ".join(v.message for v in vs)
    assert "BadStatic.xs" in msgs and "unhashable" in msgs
    assert "NotFrozen" in msgs and "frozen" in msgs


# ---------------------------------------------------------------------------
# jit-static hashability / identity regression (every registry type)
# ---------------------------------------------------------------------------

def test_plan_identity_across_fresh_solves():
    p1 = mk_planner().plan(256, 4)
    p2 = mk_planner().plan(256, 4)
    assert p1 == p2 and hash(p1) == hash(p2)
    assert len({p1, p2}) == 1


def test_taskgraph_identity_and_cached_materialization():
    g1 = lower_exec(4, "ASAS", m_e=3, r1=2)
    g2 = lower_exec(4, "ASAS", m_e=3, r1=2)
    assert g1 == g2 and hash(g1) == hash(g2)
    _ = g1.tasks                       # materialize the lazy tuple
    assert g1 == g2 and hash(g1) == hash(g2)
    assert g1 != dataclasses.replace(g1, placement_epoch=1)
    assert g1 != dataclasses.replace(g1, hot_experts=1)


def test_exec_program_identity_hints_and_modes():
    g = lower_exec(4, "ASAS", r1=2)
    hints = schedule(g, _HINT_COSTS).priority_hints()
    a = ExecProgram(g, "streams", hints)
    b = ExecProgram(g, "streams", hints)
    assert a == b and hash(a) == hash(b)
    assert a != ExecProgram(g, "streams", None)
    assert a != ExecProgram(g, "off", hints)
    assert len({a, b, ExecProgram(g, "off", hints)}) == 2


def test_placement_identity_excludes_loads():
    kw = dict(num_experts=8, num_ranks=4,
              assignment=(0, 0, 1, 1, 2, 2, 3, 3), replicated=(2,))
    a = Placement(**kw, loads=(1.0,) * 8)
    b = Placement(**kw, loads=(9.0,) * 8)      # telemetry only
    assert a == b and hash(a) == hash(b)
    assert a != Placement(**kw, epoch=1)


def test_skew_summary_identity():
    a = SkewSummary(kappa=1.25, rho=0.125, max_expert=1.5, hot_k=1)
    b = SkewSummary(kappa=1.25, rho=0.125, max_expert=1.5, hot_k=1)
    assert a == b and hash(a) == hash(b)
    assert not a.is_uniform and SkewSummary().is_uniform
    assert {a: "x"}[b] == "x"


# ---------------------------------------------------------------------------
# planner / engine validate= knobs
# ---------------------------------------------------------------------------

def test_planner_validate_accepts_real_solves():
    planner = mk_planner(validate=True)
    for S in (128, 256):
        planner.plan(S, 4)                      # must not raise


def test_engine_validate_rejects_tampered_hints(monkeypatch):
    """Acceptance: a tampered hint vector is rejected at plan time —
    before any trace sees the program."""
    pol = StaticPolicy.from_planner(mk_planner(), 64)
    orig = Plan.exec_program

    def tampered(self, *a, **kw):
        prog = orig(self, *a, **kw)
        if prog.hints is None:
            return prog
        return ExecProgram(prog.graph, prog.interleave,
                           tuple(reversed(prog.hints)))

    monkeypatch.setattr(Plan, "exec_program", tampered)
    eng = ServingEngine(CFG, num_slots=2, max_context=64,
                        plan_policy=pol, validate=True,
                        dtype=jnp.float32)
    eng.submit(Request(prompt=[1, 2, 3, 4], max_new_tokens=2))
    with pytest.raises(AnalysisError) as ei:
        eng.run()
    assert any(v.code == "hint-dep-order" for v in ei.value.violations)
    # opt-in: without validate the single-device engine never builds the
    # program, and serving is unaffected
    eng2 = ServingEngine(CFG, num_slots=2, max_context=64,
                        plan_policy=pol, dtype=jnp.float32)
    eng2.submit(Request(prompt=[1, 2, 3, 4], max_new_tokens=2))
    assert len(eng2.run()) == 1


def test_engine_validate_clean_serving_and_memo():
    pol = StaticPolicy.from_planner(mk_planner(), 64)
    eng = ServingEngine(CFG, num_slots=2, max_context=64,
                        plan_policy=pol, validate=True,
                        dtype=jnp.float32)
    eng.submit(Request(prompt=[5, 6, 7], max_new_tokens=2))
    assert len(eng.run()) == 1
    assert len(eng._validated_programs) >= 1
    before = len(eng._validated_programs)
    eng.submit(Request(prompt=[8, 9, 10], max_new_tokens=2))
    eng.run()
    assert len(eng._validated_programs) == before   # memo, not re-check


# ---------------------------------------------------------------------------
# CLI / runner surface
# ---------------------------------------------------------------------------

def test_run_all_rejects_unknown_pass():
    with pytest.raises(ValueError, match="unknown pass"):
        run_all(("nope",))


def test_cli_check_exits_zero_on_clean_pass(capsys):
    from repro.analysis.__main__ import main
    assert main(["kernelcheck", "--fast", "--check", "-q"]) == 0
    out = capsys.readouterr().out
    assert "kernelcheck: 0 violation(s)" in out


def test_analysis_error_message_lists_violations():
    err = AnalysisError([Violation("graphcheck", "deadlock", "g", "boom")])
    assert "deadlock" in str(err) and "boom" in str(err)
    assert err.violations[0].code == "deadlock"
