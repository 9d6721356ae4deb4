"""alpha-beta performance models (paper Eqs. 7-9, Fig. 7 methodology)."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import DepClusterConfig
from repro.core.perf_model import (PAPER_A6000, TPU_V5E, AlphaBeta,
                                   DepModelSpec, build_stage_models,
                                   fit_alpha_beta)

SPEC = DepModelSpec(S=2048, M=2048, H=1408, E=64, top_k=6, n_shared=2,
                    shared_H=1408, T=8, n_heads=16, d_k=128, d_v=128)
CLUSTER = DepClusterConfig(num_devices=8, ag=3, eg=5)


def test_fit_recovers_exact_line():
    xs = np.linspace(1e6, 1e9, 20)
    ts = 1.7e-4 + 8.59e-14 * xs
    model, r2 = fit_alpha_beta(xs, ts)
    assert abs(model.alpha - 1.7e-4) < 1e-9
    assert abs(model.beta - 8.59e-14) / 8.59e-14 < 1e-6
    assert r2 > 0.999999


def test_fit_r2_on_noisy_data():
    rng = np.random.RandomState(0)
    xs = np.linspace(1e6, 1e9, 50)
    ts = 1e-4 + 1e-13 * xs
    ts = ts * (1 + rng.normal(0, 0.01, ts.shape))
    _, r2 = fit_alpha_beta(xs, ts)
    # the paper reports R^2 > 0.994 for its microbenchmarks
    assert r2 > 0.99


@pytest.mark.parametrize("hw", [PAPER_A6000, TPU_V5E])
def test_stage_models_positive_and_monotone(hw):
    models = build_stage_models(hw, SPEC, CLUSTER)
    for m in (models.t_a, models.t_s, models.t_e, models.t_c):
        assert m(1) > 0
        assert m(64) > m(1)


def test_token_conservation_roundtrip():
    models = build_stage_models(PAPER_A6000, SPEC, CLUSTER)
    for m_a in (1, 4, 16):
        for r2 in (1, 2, 8):
            m_e = models.me_from_ma(m_a, r2)
            assert models.ma_from_me(m_e, r2) == pytest.approx(m_a)
            # paper constraint: m_a*ag*top_k*S == m_e*r2*E
            assert m_a * CLUSTER.ag * SPEC.top_k * SPEC.S == pytest.approx(
                m_e * r2 * SPEC.E)


@given(alpha=st.floats(1e-6, 1e-2), beta=st.floats(1e-16, 1e-10),
       x=st.floats(1.0, 1e12))
@settings(max_examples=50, deadline=None)
def test_alpha_beta_affine(alpha, beta, x):
    m = AlphaBeta(alpha, beta)
    assert m(x) == pytest.approx(alpha + beta * x)
    s = m.scaled(3)
    assert s(x) == pytest.approx(3 * alpha + 3 * beta * x)


def test_shared_expert_zero_when_absent():
    spec = DepModelSpec(S=2048, M=2048, H=1408, E=64, top_k=6, n_shared=0,
                        shared_H=0, T=8, n_heads=16, d_k=128, d_v=128)
    models = build_stage_models(PAPER_A6000, spec, CLUSTER)
    assert models.spec.n_shared == 0
