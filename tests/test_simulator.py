"""Exact event-order simulator vs the paper's closed forms (Eq. 13)."""
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.analytic import (ORDER_AASS, ORDER_ASAS, StageTimes,
                                 makespan_closed_form, makespan_naive,
                                 makespan_pppipe)
from repro.core.simulator import (non_overlapped_comm_time, simulate_dep,
                                  simulate_naive, simulate_pppipe, _subtract,
                                  _union, total_len)

ST = StageTimes(t_a=0.013, t_s=0.012, t_e=0.011, t_c=0.004)


def test_exact_match_r2_1_asas():
    """For r2 = 1 the paper's Eq. 13 is exact (we verified the recurrences
    collapse); the simulator must agree to float precision."""
    for r1 in (1, 2, 4, 8):
        a = makespan_closed_form(ST, 8, r1, 1, ORDER_ASAS)
        s = simulate_dep(ST, 8, r1, 1, order=ORDER_ASAS).makespan
        assert a == pytest.approx(s, rel=1e-9), (r1,)


@given(t_a=st.floats(1e-4, 5e-2), t_s=st.floats(0.0, 5e-2),
       t_e=st.floats(1e-4, 5e-2), t_c=st.floats(1e-5, 5e-2),
       r1=st.integers(1, 6), r2=st.integers(1, 6), T=st.integers(1, 6))
@settings(max_examples=200, deadline=None)
def test_analytic_upper_bounds_simulation(t_a, t_s, t_e, t_c, r1, r2, T):
    """Eq. 13 is a (tight) conservative model: it never under-estimates the
    exact event-order makespan, and is within 25% of it. (The gap comes
    from the extra (r2-1)Y term in Eq. 13 — see EXPERIMENTS.md.)"""
    stt = StageTimes(t_a=t_a, t_s=t_s, t_e=t_e, t_c=t_c)
    a = makespan_closed_form(stt, T, r1, r2, ORDER_ASAS)
    s = simulate_dep(stt, T, r1, r2, order=ORDER_ASAS).makespan
    assert a >= s - 1e-12
    # Eq. 13's slack is exactly the double-counted (r2-1)*Y term (G already
    # includes it) plus small fill-phase conservatism
    Y = max(t_e, t_c)
    assert a <= s * 1.05 + (r2 - 1) * Y + 1e-9


@given(t_a=st.floats(1e-4, 5e-2), t_s=st.floats(0.0, 5e-2),
       t_e=st.floats(1e-4, 5e-2), t_c=st.floats(1e-5, 5e-2),
       r1=st.integers(1, 6), r2=st.integers(1, 6), T=st.integers(1, 5))
@settings(max_examples=200, deadline=None)
def test_aass_closed_form_bounds(t_a, t_s, t_e, t_c, r1, r2, T):
    stt = StageTimes(t_a=t_a, t_s=t_s, t_e=t_e, t_c=t_c)
    a = makespan_closed_form(stt, T, r1, r2, ORDER_AASS)
    s = simulate_dep(stt, T, r1, r2, order=ORDER_AASS).makespan
    # the AASS closed form is a two-sided approximation (observed in
    # [0.85, 1.0] x exact over 20k random workloads); the solver's hybrid
    # mode re-ranks its top-K with the exact simulator, so this only needs
    # to be a sane ranking heuristic.
    Y = max(t_e, t_c)
    assert a >= 0.8 * s - 1e-12
    assert a <= s * 1.1 + (r2 - 1) * Y + r1 * max(t_a, t_s) + 1e-9


def test_naive_closed_form_exact():
    for T in (1, 4, 9):
        assert makespan_naive(ST, T) == pytest.approx(
            simulate_naive(ST, T).makespan, rel=1e-12)


def test_pppipe_closed_form_exact():
    for T in (1, 3, 8):
        for r1 in (1, 2, 4):
            a = makespan_pppipe(ST, T, r1)
            s = simulate_pppipe(ST, T, r1).makespan
            assert a == pytest.approx(s, rel=1e-9), (T, r1)


def test_resource_exclusivity_and_dependencies():
    """Rules 1-9 of Eq. 5: no overlapping intervals per resource; chunk
    stages in order."""
    res = simulate_dep(ST, 4, 3, 2, order=ORDER_ASAS,
                       record_intervals=True)
    for name, iv in res.intervals.items():
        iv_sorted = sorted(iv)
        for (s1, e1), (s2, e2) in zip(iv_sorted, iv_sorted[1:]):
            assert s2 >= e1 - 1e-12, (name, (s1, e1), (s2, e2))
    # makespan equals the max interval end
    ends = [e for iv in res.intervals.values() for _, e in iv]
    assert res.makespan == pytest.approx(max(ends))


def test_pipelining_beats_sequential():
    """PPPipe < naive; FinDEP (shared not blocking a2e) <= PPPipe at the
    same granularity. NOTE: per-chunk durations must be scaled when
    comparing different r2 (StageTimes are per-chunk)."""
    T = 8
    r1 = 4
    # StageTimes are per-micro-batch: the naive baseline runs the WHOLE
    # mini-batch at once, i.e. r1 x every duration (alpha-free scaling).
    full = StageTimes(t_a=ST.t_a * r1, t_s=ST.t_s * r1, t_e=ST.t_e * r1,
                      t_c=ST.t_c * r1)
    naive = simulate_naive(full, T).makespan
    pp = simulate_pppipe(ST, T, r1).makespan
    fd = simulate_dep(ST, T, r1, 1, order=ORDER_ASAS).makespan
    assert pp < naive
    assert fd <= pp + 1e-12
    # (a specific r2>1 config is NOT pointwise guaranteed to beat PPPipe —
    # only the optimum over FinDEP's search space is; see test_solver.)


def test_interval_algebra():
    assert _union([(0, 1), (0.5, 2), (3, 4)]) == [(0, 2), (3, 4)]
    assert total_len([(0, 2), (3, 4)]) == pytest.approx(3.0)
    a = [(0.0, 10.0)]
    b = [(2.0, 3.0), (5.0, 7.0)]
    assert _subtract(a, b) == [(0.0, 2.0), (3.0, 5.0), (7.0, 10.0)]


def test_non_overlapped_comm_decreases_with_overlap():
    """Table 7's metric: FinDEP exposes less communication than naive."""
    slow_comm = StageTimes(t_a=0.01, t_s=0.008, t_e=0.01, t_c=0.02)
    T = 6
    nv = non_overlapped_comm_time(
        simulate_naive(slow_comm, T, record_intervals=True))
    pp = non_overlapped_comm_time(
        simulate_pppipe(slow_comm, T, 4, record_intervals=True))
    quarter = StageTimes(t_a=slow_comm.t_a, t_s=slow_comm.t_s,
                         t_e=slow_comm.t_e / 4, t_c=slow_comm.t_c / 4)
    fd = non_overlapped_comm_time(
        simulate_dep(quarter, T, 4, 4, order=ORDER_ASAS,
                     record_intervals=True))
    assert fd <= pp <= nv + 1e-12
