"""FinDEP Algorithm 1: optimality vs brute force, theorem validation,
solver latency (< 1 s claim)."""
import time

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import DepClusterConfig
from repro.core.analytic import ORDER_ASAS, ORDERS
from repro.core.baselines import best_pppipe, naive_plan
from repro.core.perf_model import (PAPER_A6000, TPU_V5E, AlphaBeta,
                                   DepModelSpec, HardwareProfile,
                                   build_stage_models)
from repro.core.solver import (get_max_r1, max_r2, solve, solve_brute_force,
                               solve_r2)


def models_for(S=2048, n_shared=2, hw=PAPER_A6000, ag=3, eg=5, E=64,
               top_k=6):
    spec = DepModelSpec(S=S, M=2048, H=1408, E=E, top_k=top_k,
                        n_shared=n_shared, shared_H=1408, T=8, n_heads=16,
                        d_k=128, d_v=128)
    cluster = DepClusterConfig(num_devices=ag + eg, ag=ag, eg=eg)
    return build_stage_models(hw, spec, cluster), spec.T


@pytest.mark.parametrize("n_shared,hw", [(2, PAPER_A6000), (0, PAPER_A6000),
                                         (2, TPU_V5E)])
def test_solver_matches_brute_force(n_shared, hw):
    models, T = models_for(n_shared=n_shared, hw=hw)
    plan, _ = solve(models, T, mem_cap_samples=12, objective="simulate",
                    r2_cap=12, r1_cap=12)
    bf = solve_brute_force(models, T, 12, objective="simulate", r2_cap=12,
                           r1_cap=12)
    assert plan.throughput == pytest.approx(bf.throughput, rel=1e-9)


def test_hybrid_at_least_as_good_as_analytic_choice():
    models, T = models_for()
    p_h, _ = solve(models, T, 16, objective="hybrid")
    p_a, _ = solve(models, T, 16, objective="analytic")
    # evaluate both final plans under the exact simulator
    from repro.core.solver import _throughput
    tps_h, _ = _throughput(models, T, p_h.m_a, p_h.r1, p_h.r2, p_h.order,
                           "simulate")
    tps_a, _ = _throughput(models, T, p_a.m_a, p_a.r1, p_a.r2, p_a.order,
                           "simulate")
    assert tps_h >= tps_a - 1e-9


def test_theorem1_2_monotone_in_ma():
    """Throughput (with per-m_a optimized r2) increases with m_a (Thm 1-2,
    Table 3)."""
    models, T = models_for()
    prev = 0.0
    for m_a in (1, 2, 4, 8, 16):
        r2, tps, _ = solve_r2(models, T, m_a, r1=1, order=ORDER_ASAS,
                              objective="analytic")
        assert tps >= prev - 1e-9, (m_a, tps, prev)
        prev = tps


def test_theorem3_monotone_in_r1():
    """Throughput non-decreasing in r1 (Thm 3, Table 4)."""
    models, T = models_for()
    prev = 0.0
    for r1 in (1, 2, 4, 8):
        r2, tps, _ = solve_r2(models, T, m_a=2, r1=r1, order=ORDER_ASAS,
                              objective="analytic")
        assert tps >= prev - 1e-9, (r1, tps, prev)
        prev = tps


def test_theorem4_unimodal_in_r2():
    """Eq. 17 convex in 1/r2 => throughput unimodal in integer r2."""
    models, T = models_for()
    from repro.core.solver import _throughput
    tps = [_throughput(models, T, 8, 2, r2, ORDER_ASAS, "analytic")[0]
           for r2 in range(1, max_r2(models, 8, 32) + 1)]
    peak = tps.index(max(tps))
    assert all(tps[i] <= tps[i + 1] + 1e-12 for i in range(peak)), tps
    assert all(tps[i] >= tps[i + 1] - 1e-12 for i in range(peak, len(tps) - 1))


def test_findep_beats_or_ties_pppipe_and_naive():
    """The paper's headline ordering: FinDEP >= best PPPipe >= naive.
    Holds structurally: FinDEP's search space contains PPPipe's schedules
    relaxed (shared no longer blocks a2e) and naive is PPPipe(r1=1)."""
    for hw in (PAPER_A6000, TPU_V5E):
        for n_shared in (0, 2):
            models, T = models_for(n_shared=n_shared, hw=hw)
            fd, _ = solve(models, T, 16, objective="simulate", r2_cap=8,
                          r1_cap=16)
            pp = best_pppipe(models, T, 16, r1_cap=16)
            nv = naive_plan(models, T, 16)
            assert fd.throughput >= pp.throughput * (1 - 1e-9)
            assert pp.throughput >= nv.throughput * (1 - 1e-9)


def test_solver_under_one_second():
    """Paper §5.4: 'the solver completes in under 1 second'."""
    models, T = models_for()
    t0 = time.perf_counter()
    plan, stats = solve(models, T, mem_cap_samples=64, objective="hybrid")
    dt = time.perf_counter() - t0
    assert dt < 1.0, dt
    assert plan.throughput > 0


def test_get_max_r1_memory_constraint():
    assert get_max_r1(4, 16) == 4
    assert get_max_r1(5, 16) == 3
    assert get_max_r1(17, 16) == 0
    assert get_max_r1(1, 16, r1_cap=8) == 8


def test_fixed_batch_mode():
    """Online mode: r1 * m_a must cover the arrived batch exactly."""
    models, T = models_for()
    plan, _ = solve(models, T, 16, objective="analytic", fixed_batch=12)
    assert plan.m_a * plan.r1 == 12


@given(seq=st.sampled_from([512, 1024, 2048, 4096, 8192]),
       n_shared=st.integers(0, 4), eg=st.integers(2, 7))
@settings(max_examples=20, deadline=None)
def test_solver_feasible_across_workloads(seq, n_shared, eg):
    models, T = models_for(S=seq, n_shared=n_shared, ag=8 - eg, eg=eg)
    plan, _ = solve(models, T, 8, objective="analytic")
    assert plan.r1 * plan.m_a <= 8
    assert plan.r2 >= 1 and plan.m_e >= 1
    assert plan.order in ORDERS
