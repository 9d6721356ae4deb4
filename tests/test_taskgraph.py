"""Task-graph IR: golden topology, legacy-simulator parity, executor
walk order, per-primitive breakdowns, and executor bit-parity."""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs.base import DepClusterConfig
from repro.core.analytic import ORDER_AASS, ORDER_ASAS, StageTimes
from repro.core.perf_model import (PAPER_A6000, TPU_V5E, DepModelSpec,
                                   build_stage_models)
from repro.core.simulator import simulate_dep
from repro.core.solver import Plan, plan_breakdown, solve
from repro.core.taskgraph import (A2E, ATTN, E2A, EXP, GATE, SHARED,
                                  LoweringSpec, TaskCosts, ascii_gantt,
                                  lower, lower_exec, schedule)

ST = StageTimes(t_a=0.013, t_s=0.012, t_e=0.011, t_c=0.004)


def _plan(r1, r2, order, m_e=1):
    return Plan(m_a=1, r1=r1, r2=r2, m_e=m_e, order=order,
                throughput=0.0, makespan=0.0)


def _models(S=2048, n_shared=2, hw=PAPER_A6000, ag=3, eg=5):
    """Table 5/7-style stage models (DeepSeek-V2-Lite dimensions on the
    paper's testbed-A cluster split)."""
    spec = DepModelSpec(S=S, M=2048, H=1408, E=64, top_k=6,
                        n_shared=n_shared, shared_H=1408, T=8, n_heads=16,
                        d_k=128, d_v=128)
    cluster = DepClusterConfig(num_devices=ag + eg, ag=ag, eg=eg)
    return build_stage_models(hw, spec, cluster), spec.T


# ---------------------------------------------------------------------------
# Golden topology
# ---------------------------------------------------------------------------


def test_golden_topology_asas():
    """ASAS: shared expert split into r2 segments per (layer, mb), one at
    each chunk boundary; a2e independent of shared (FinDEP rule 7)."""
    T, r1, r2 = 2, 2, 3
    g = lower(_plan(r1, r2, ORDER_ASAS), LoweringSpec(T=T))
    counts = {k: len(g.tasks_of(k)) for k in (ATTN, GATE, SHARED, A2E,
                                              EXP, E2A)}
    assert counts == {ATTN: T * r1, GATE: T * r1,
                      SHARED: T * r1 * r2,               # r2 segments
                      A2E: T * r1 * r2, EXP: T * r1 * r2,
                      E2A: T * r1 * r2}
    assert g.shared_segments == r2
    # every shared segment boundary 0..r2-1 appears once per (t, i)
    for t in range(T):
        for i in range(r1):
            bounds = sorted(task.chunk for _, task in
                            g.tasks_of(SHARED, layer=t, mb=i))
            assert bounds == list(range(r2))
    # FinDEP: no a2e task depends on any SHARED task
    shared_ids = {idx for idx, _ in g.tasks_of(SHARED)}
    for idx, task in g.tasks_of(A2E):
        assert not (set(task.deps) & shared_ids), (idx, task)
    g.validate()


def test_golden_topology_aass():
    """AASS: one whole-batch shared task per (layer, mb) at boundary 0."""
    T, r1, r2 = 2, 3, 4
    g = lower(_plan(r1, r2, ORDER_AASS), LoweringSpec(T=T))
    assert len(g.tasks_of(SHARED)) == T * r1
    assert all(task.chunk == 0 for _, task in g.tasks_of(SHARED))
    assert g.shared_segments == 1
    # AG lane order within a layer: all ATTN before all SHARED
    ag0 = [t for t in g.tasks if t.layer == 0 and t.resource == "AG"]
    first_shared = next(i for i, t in enumerate(ag0) if t.kind == SHARED)
    assert all(t.kind != ATTN for t in ag0[first_shared:])
    g.validate()


def test_golden_topology_blocking_and_no_shared():
    """naive/PPPipe lowering: a2e waits on the last shared segment;
    has_shared=False drops SHARED (and the dep)."""
    g = lower(_plan(2, 1, ORDER_ASAS),
              LoweringSpec(T=1, shared_blocks_a2e=True))
    shared_ids = {idx for idx, _ in g.tasks_of(SHARED)}
    for _, task in g.tasks_of(A2E):
        assert set(task.deps) & shared_ids, task
    g2 = lower(_plan(2, 2, ORDER_ASAS), LoweringSpec(T=2, has_shared=False))
    assert not g2.tasks_of(SHARED) and not g2.has_shared
    g2.validate()


def test_cross_layer_deps():
    """A(t+1, i) depends on (t, i)'s last e2a AND last shared segment."""
    T, r1, r2 = 3, 2, 2
    g = lower(_plan(r1, r2, ORDER_ASAS), LoweringSpec(T=T))
    for t in range(1, T):
        for i in range(r1):
            (a_idx, a_task), = g.tasks_of(ATTN, layer=t, mb=i)
            dep_kinds = {g.tasks[d].kind for d in a_task.deps}
            assert dep_kinds == {E2A, SHARED}
            for d in a_task.deps:
                assert g.tasks[d].layer == t - 1
                assert g.tasks[d].mb == i


def test_lowering_is_cached():
    """Equal (plan, spec) lower to the SAME object (lru-cached) — jit
    static-arg reuse never retraces for an identical schedule."""
    a = lower(_plan(2, 3, ORDER_ASAS), LoweringSpec(T=4))
    b = lower(_plan(2, 3, ORDER_ASAS), LoweringSpec(T=4))
    assert a is b
    assert lower_exec(3, ORDER_ASAS, 2) is lower_exec(3, ORDER_ASAS, 2)
    assert hash(a) == hash(b)
    assert a != lower(_plan(2, 3, ORDER_AASS), LoweringSpec(T=4))


# ---------------------------------------------------------------------------
# Executor walk order
# ---------------------------------------------------------------------------


def test_exec_walk_order_asas():
    walk = lower_exec(2, ORDER_ASAS).exec_walk()
    assert [(t.kind, t.chunk) for t in walk] == [
        (GATE, 0), (A2E, 0), (SHARED, 0), (EXP, 0), (E2A, 0),
        (A2E, 1), (SHARED, 1), (EXP, 1), (E2A, 1)]


def test_exec_walk_order_aass():
    walk = lower_exec(2, ORDER_AASS).exec_walk()
    assert [(t.kind, t.chunk) for t in walk] == [
        (GATE, 0), (A2E, 0), (SHARED, 0), (EXP, 0), (E2A, 0),
        (A2E, 1), (EXP, 1), (E2A, 1)]


def test_exec_graph_collapses_plan_identity():
    """Plans that differ only in modeled throughput/batching share one
    exec graph (bounded retraces)."""
    p1 = Plan(m_a=4, r1=2, m_e=3.7, r2=2, order=ORDER_ASAS,
              throughput=10.0, makespan=1.0)
    p2 = Plan(m_a=8, r1=1, m_e=3.2, r2=2, order=ORDER_ASAS,
              throughput=99.0, makespan=2.0)
    assert p1.exec_graph() is p2.exec_graph()
    assert p1.exec_graph().m_e == 3


# ---------------------------------------------------------------------------
# Parity: generic graph scheduler vs the legacy simulator recurrence
# ---------------------------------------------------------------------------


def _legacy_simulate_dep(st, T, r1, r2, order="ASAS",
                         shared_blocks_a2e=False):
    """The pre-refactor hand-written forward recurrence (verbatim)."""
    has_shared = st.t_s > 0.0
    if not has_shared:
        seq = [("A", i) for i in range(r1)]
    elif order == "ASAS":
        seq = [p for i in range(r1) for p in (("A", i), ("S", i))]
    else:
        seq = ([("A", i) for i in range(r1)]
               + [("S", i) for i in range(r1)])
    ag_free = a2e_free = eg_free = e2a_free = 0.0
    prev_ready = [0.0] * r1
    busy = {k: 0.0 for k in ("AG", "A2E", "EG", "E2A")}
    a_end = [0.0] * r1
    s_end = [0.0] * r1
    for _t in range(T):
        for kind, i in seq:
            if kind == "A":
                end = max(ag_free, prev_ready[i]) + st.t_a
                busy["AG"] += st.t_a
                a_end[i] = end
            else:
                end = max(ag_free, a_end[i]) + st.t_s
                busy["AG"] += st.t_s
                s_end[i] = end
            ag_free = end
        if not has_shared:
            for i in range(r1):
                s_end[i] = a_end[i]
        e2a_last = [0.0] * r1
        for i in range(r1):
            gate = s_end[i] if (shared_blocks_a2e and has_shared) \
                else a_end[i]
            for _j in range(r2):
                a2e_free = max(a2e_free, gate) + st.t_c
                busy["A2E"] += st.t_c
                eg_free = max(eg_free, a2e_free) + st.t_e
                busy["EG"] += st.t_e
                e2a_free = max(e2a_free, eg_free) + st.t_c
                busy["E2A"] += st.t_c
            e2a_last[i] = e2a_free
        for i in range(r1):
            prev_ready[i] = max(e2a_last[i], s_end[i])
    return max(max(e2a_last), max(s_end)), busy


@pytest.mark.parametrize("hw", [PAPER_A6000, TPU_V5E])
@pytest.mark.parametrize("S", [1024, 2048, 4096])
def test_parity_table_shapes(S, hw):
    """Graph-scheduler makespan == legacy simulator on the Table 5/7
    shapes (DeepSeek dims, both testbeds, solved plans per shape)."""
    models, T = _models(S=S, hw=hw)
    plan, _ = solve(models, T, mem_cap_samples=4, r1_cap=4, r2_cap=32)
    for r1, r2, order in [(plan.r1, plan.r2, plan.order), (1, 1, "ASAS"),
                          (4, 1, "ASAS"), (2, 8, "AASS"), (4, 4, "ASAS")]:
        st = StageTimes.from_models(models, plan.m_a,
                                    models.me_from_ma(plan.m_a, r2))
        legacy_ms, legacy_busy = _legacy_simulate_dep(st, T, r1, r2, order)
        res = simulate_dep(st, T, r1, r2, order=order)
        assert res.makespan == pytest.approx(legacy_ms, rel=1e-12), \
            (S, r1, r2, order)
        for k, v in legacy_busy.items():
            assert res.busy[k] == pytest.approx(v, rel=1e-12), k


def test_parity_randomized(rng):
    """Randomized stage times / shapes / lowering flags."""
    for _ in range(300):
        st = StageTimes(t_a=rng.uniform(1e-4, 5e-2),
                        t_s=float(rng.choice([0.0,
                                              rng.uniform(1e-4, 5e-2)])),
                        t_e=rng.uniform(1e-4, 5e-2),
                        t_c=rng.uniform(1e-5, 5e-2))
        T = int(rng.randint(1, 6))
        r1 = int(rng.randint(1, 6))
        r2 = int(rng.randint(1, 6))
        order = str(rng.choice(["ASAS", "AASS"]))
        blk = bool(rng.randint(0, 2))
        legacy_ms, _ = _legacy_simulate_dep(st, T, r1, r2, order, blk)
        res = simulate_dep(st, T, r1, r2, order=order,
                           shared_blocks_a2e=blk)
        assert res.makespan == pytest.approx(legacy_ms, rel=1e-12)


def test_makespan_fastpath_parity(rng):
    """``simulate_makespan`` (vectorized lane recurrence, the solver's
    simulate objective) agrees with the generic list scheduler across
    randomized stage times, shapes, orders, and lowering flags."""
    from repro.core.simulator import simulate_makespan
    for _ in range(300):
        st = StageTimes(t_a=rng.uniform(1e-4, 5e-2),
                        t_s=float(rng.choice([0.0,
                                              rng.uniform(1e-4, 5e-2)])),
                        t_e=rng.uniform(1e-4, 5e-2),
                        t_c=rng.uniform(1e-5, 5e-2))
        T = int(rng.randint(1, 6))
        r1 = int(rng.randint(1, 6))
        r2 = int(rng.randint(1, 6))
        order = str(rng.choice(["ASAS", "AASS"]))
        blk = bool(rng.randint(0, 2))
        exact = simulate_dep(st, T, r1, r2, order=order,
                             shared_blocks_a2e=blk).makespan
        fast = simulate_makespan(st, T, r1, r2, order=order,
                                 shared_blocks_a2e=blk)
        assert fast == pytest.approx(exact, rel=1e-9), \
            (T, r1, r2, order, blk)


def test_scheduler_invariants():
    """Per-resource mutual exclusion; makespan = max interval end; the
    scheduled SimResult exposes the underlying graph schedule."""
    res = simulate_dep(ST, 4, 3, 2, order=ORDER_ASAS,
                       record_intervals=True)
    for name, iv in res.intervals.items():
        for (s1, e1), (s2, e2) in zip(iv, iv[1:]):
            assert s2 >= e1 - 1e-12, (name, (s1, e1), (s2, e2))
    ends = [e for iv in res.intervals.values() for _, e in iv]
    assert res.makespan == pytest.approx(max(ends))
    assert res.scheduled is not None
    assert res.scheduled.makespan == res.makespan


# ---------------------------------------------------------------------------
# Per-primitive breakdowns (telemetry tags)
# ---------------------------------------------------------------------------


def test_breakdown_classes_sum_to_busy():
    g = lower(_plan(2, 3, ORDER_ASAS), LoweringSpec(T=4))
    res = schedule(g, TaskCosts.from_stage_times(ST))
    bd = res.breakdown()
    total_busy = sum(res.busy.values())
    assert bd.total == pytest.approx(total_busy, rel=1e-12)
    # comm class == both link lanes; attn == t_a tasks
    assert bd.comm == pytest.approx(res.busy["A2E"] + res.busy["E2A"])
    assert bd.attn == pytest.approx(4 * 2 * ST.t_a)
    assert bd.gemm == pytest.approx(res.busy["EG"] + 4 * 2 * ST.t_s)


def test_solver_attaches_normalized_breakdown():
    models, T = _models()
    plan, _ = solve(models, T, mem_cap_samples=4, r1_cap=4, r2_cap=16)
    assert plan.breakdown is not None
    assert plan.breakdown.total == pytest.approx(plan.makespan, rel=1e-9)
    # reproducible from the public helper
    again = plan_breakdown(models, T, plan)
    assert again.as_dict() == pytest.approx(plan.breakdown.as_dict())


def test_baseline_plans_carry_breakdown():
    from repro.core.baselines import (best_pppipe, eps_pipeline_plan,
                                      naive_plan)
    models, T = _models()
    for p in (naive_plan(models, T, 4), best_pppipe(models, T, 4, r1_cap=4),
              eps_pipeline_plan(models, T, 4)):
        assert p.breakdown is not None
        assert p.breakdown.total == pytest.approx(p.makespan, rel=1e-9)


def test_ascii_gantt_renders():
    g = lower(_plan(2, 2, ORDER_ASAS), LoweringSpec(T=2))
    out = ascii_gantt(schedule(g, TaskCosts.from_stage_times(ST)), width=60)
    lines = out.splitlines()
    assert len(lines) == 5 and lines[0].lstrip().startswith("AG")
    assert "E" in lines[2] and ">" in lines[1] and "<" in lines[3]


# ---------------------------------------------------------------------------
# Executor bit-parity: graph walker vs the pre-refactor loop (subprocess,
# 4 virtual devices; plain Mesh — no AxisType dependence)
# ---------------------------------------------------------------------------

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    env.setdefault("JAX_PLATFORMS", "cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


@pytest.mark.slow
def test_executor_bit_parity_graph_vs_legacy_loop():
    """The graph walker emits the SAME op sequence as the pre-refactor
    hand-rolled chunk loop: sequence-mode outputs are bit-identical."""
    out = run_sub(textwrap.dedent("""
        import dataclasses, numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.models import moe as moe_lib
        from repro.models.layers import mlp_apply
        from repro.models.transformer import ExecutionContext
        from repro.core import dep
        from repro.core.solver import Plan
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh

        # ---- the pre-refactor executor loop, verbatim ----------------
        def legacy_shared_schedule(order, shared_fn, shared_x, r2):
            if shared_fn is None:
                return lambda j: None
            if order == "ASAS":
                seg = shared_x.shape[0] // r2
                def emit(j):
                    lo = j * seg
                    hi = (shared_x.shape[0] if j == r2 - 1
                          else (j + 1) * seg)
                    return shared_fn(shared_x[lo:hi])
            else:
                def emit(j):
                    return shared_fn(shared_x) if j == 0 else None
            return emit

        def legacy_chunked(buffers, expert_params, axis, r2,
                           shared_fn=None, shared_x=None, order="AASS"):
            E_pad, C_loc, M = buffers.shape
            chunk = C_loc // r2
            def a2e(buf):
                return jax.lax.all_to_all(buf, axis, split_axis=0,
                                          concat_axis=1, tiled=True)
            def e2a(out):
                return jax.lax.all_to_all(out, axis, split_axis=1,
                                          concat_axis=0, tiled=True)
            emit = legacy_shared_schedule(order, shared_fn, shared_x, r2)
            outs, shared_parts = [], []
            for j in range(r2):
                buf = jax.lax.dynamic_slice_in_dim(buffers, j * chunk,
                                                   chunk, 1)
                dispatched = a2e(buf)
                part = emit(j)
                if part is not None:
                    shared_parts.append(part)
                outs.append(e2a(moe_lib.expert_ffn(expert_params,
                                                   dispatched)))
            shared_out = (jnp.concatenate(shared_parts, axis=0)
                          if shared_parts else None)
            return jnp.concatenate(outs, axis=1), shared_out
        # --------------------------------------------------------------

        mesh = make_mesh((2, 2), ("data", "model"))
        cfg = get_smoke_config("qwen2-moe-a2.7b")
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=8.0))
        key = jax.random.PRNGKey(1)
        params = moe_lib.moe_init(key, cfg.d_model, cfg.moe, 4)
        x = jax.random.normal(key, (4, 8, cfg.d_model), jnp.float32)
        ctx = ExecutionContext(mesh=mesh, moe_impl="dep")
        mcfg = cfg.moe
        E_pad = 4
        for r2, order, m_e in [(1, "AASS", 1), (2, "ASAS", 1),
                               (4, "AASS", 1), (2, "AASS", 3),
                               (4, "ASAS", 1)]:
            plan = Plan(m_a=1, r1=1, m_e=m_e, r2=r2, order=order,
                        throughput=0, makespan=0)
            with mesh:
                y_new, _ = jax.jit(lambda p, xx: dep.moe_apply_dep(
                    p, xx, mcfg, ctx, E_pad,
                    plan=plan.exec_graph()))(params, x)

            # legacy reference through an identical shard_map harness
            def local(x_loc, router_loc, experts_loc, shared_loc):
                Bl, Sl, M = x_loc.shape
                xf = x_loc.reshape(-1, M)
                cap = moe_lib.expert_capacity(xf.shape[0], mcfg, E_pad,
                                              multiple_of=r2 * m_e)
                info = moe_lib.moe_dispatch({"router": router_loc}, xf,
                                            mcfg, cap, E_pad)
                shared_fn = lambda xs: mlp_apply(shared_loc, xs)
                out, shared_out = legacy_chunked(
                    info.buffers, experts_loc, "model", r2,
                    shared_fn=shared_fn, shared_x=xf, order=order)
                y = moe_lib.moe_combine(info, out, xf.shape[0],
                                        x_loc.dtype)
                if shared_out is not None:
                    y = y + shared_out
                aux = jax.lax.psum(info.aux, ("data", "model")) / 4
                return y.reshape(Bl, Sl, M), aux

            in_spec = P("data", "model", None)
            with mesh:
                y_old, _ = jax.jit(jax.shard_map(
                    local, mesh=mesh,
                    in_specs=(in_spec,
                              jax.tree.map(lambda _: P(),
                                           params["router"]),
                              jax.tree.map(lambda _: P("model", None,
                                                       None),
                                           params["experts"]),
                              jax.tree.map(lambda _: P(),
                                           params["shared"])),
                    out_specs=(in_spec, P()),
                    check_vma=False))(x, params["router"],
                                      params["experts"], params["shared"])
            diff = float(jnp.max(jnp.abs(y_new - y_old)))
            assert diff == 0.0, (r2, order, m_e, diff)
            print("bitpar ok", r2, order, m_e)
    """))
    assert out.count("bitpar ok") == 5


# ---------------------------------------------------------------------------
# Stream-aware lowering: exec_streams / exec_interleaved / priority hints
# ---------------------------------------------------------------------------


def test_exec_streams_groups_walk_by_mb():
    from repro.core.taskgraph import ExecProgram
    g = lower_exec(2, ORDER_ASAS, r1=3)
    streams = g.exec_streams()
    assert len(streams) == 3
    shape0 = [(t.kind, t.chunk) for t in streams[0]]
    for i, s in enumerate(streams):
        assert all(t.mb == i for t in s)
        assert [(t.kind, t.chunk) for t in s] == shape0
    # the "off" program is exactly the streams run back-to-back
    off = ExecProgram(g, interleave="off").walk()
    assert off == tuple(t for s in streams for t in s)


def test_exec_interleaved_is_dep_safe_and_interleaves():
    from repro.core.taskgraph import ExecProgram
    g = lower_exec(2, ORDER_ASAS, r1=3)
    off = ExecProgram(g, interleave="off").walk()
    inter = ExecProgram(g, interleave="streams").walk()
    # same task multiset, genuinely reordered across streams: some
    # later-stream task is emitted before an earlier stream retires
    key = lambda t: (t.mb, t.kind, t.chunk)
    assert sorted(map(key, inter)) == sorted(map(key, off))
    mbs = [t.mb for t in inter]
    assert mbs != sorted(mbs), "streams were not interleaved"
    # emission respects every dependency edge (positions via identity
    # on the graph's task list)
    pos = {}
    for p, t in enumerate(inter):
        pos[next(i for i, u in enumerate(g.tasks) if u is t)] = p
    for i, t in enumerate(g.tasks):
        if i in pos:
            for d in t.deps:
                if d in pos:
                    assert pos[d] < pos[i], (d, i)


def test_exec_interleaved_rejects_bad_hints():
    g = lower_exec(2, ORDER_ASAS, r1=2)
    with pytest.raises(ValueError, match="hints length"):
        g.exec_interleaved(hints=(0, 1, 2))
    n = len(g.tasks)
    reverse = tuple(range(n - 1, -1, -1))   # dep-inverting priority
    with pytest.raises(ValueError, match="dep-consistent"):
        g.exec_interleaved(hints=reverse)


def test_priority_hints_rank_scheduled_starts():
    g = lower_exec(2, ORDER_ASAS, r1=2)
    sched = schedule(g, TaskCosts.from_stage_times(ST))
    hints = sched.priority_hints()
    assert sorted(hints) == list(range(len(g.tasks)))
    order = sorted(range(len(hints)), key=lambda i: hints[i])
    starts = [sched.starts[i] for i in order]
    assert starts == sorted(starts)


def test_exec_program_static_arg_semantics():
    from repro.core.taskgraph import ExecProgram
    g = lower_exec(2, ORDER_ASAS, 3, r1=2)
    p = ExecProgram(g, interleave="streams")
    assert hash(p) == hash(ExecProgram(g, interleave="streams"))
    assert p != ExecProgram(g, interleave="off")
    assert p.streams == 2
    # capacity alignment is the full (stream, chunk, m_e) grid in BOTH
    # modes — that equality is what makes them bit-identical
    assert p.capacity_multiple == 2 * 2 * 3
    assert ExecProgram(g, interleave="off").capacity_multiple == 2 * 2 * 3
    with pytest.raises(ValueError, match="interleave"):
        ExecProgram(g, interleave="sideways")


def test_stream_serial_deps_and_major_order():
    from repro.core.taskgraph import (stream_major_order,
                                      stream_serial_deps)
    g = lower_exec(2, ORDER_ASAS, r1=3)
    extra = stream_serial_deps(g)
    firsts = {}
    for i, t in enumerate(g.tasks):
        firsts.setdefault(t.mb, i)
    # one serialization point per stream after the first
    assert set(extra) == {firsts[1], firsts[2]}
    for mb, first in firsts.items():
        if mb == 0:
            continue
        dep_tasks = [g.tasks[d] for d in extra[first]]
        assert all(t.mb == mb - 1 for t in dep_tasks)
        # one "last task" per lane the previous stream used
        lanes = {t.resource for t in dep_tasks}
        assert len(dep_tasks) == len(lanes)
    order = stream_major_order(g)
    assert sorted(order) == list(range(len(g.tasks)))
    mbs = [g.tasks[i].mb for i in order]
    assert mbs == sorted(mbs)
