"""MoE layer invariants: routing, dispatch/combine, capacity, padding."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import MoEConfig
from repro.models import moe as moe_lib

KEY = jax.random.PRNGKey(0)


def mk(E=8, top_k=2, H=64, M=32, n_shared=0, cf=8.0):
    mcfg = MoEConfig(num_experts=E, top_k=top_k, expert_ffn_dim=H,
                     num_shared_experts=n_shared, shared_ffn_dim=H,
                     capacity_factor=cf)
    params = moe_lib.moe_init(KEY, M, mcfg)
    return mcfg, params


def test_routing_topk_properties():
    mcfg, params = mk()
    x = jax.random.normal(KEY, (64, 32), jnp.float32)
    r = moe_lib.route_topk(params["router"], x, mcfg)
    assert r.experts.shape == (64, 2)
    assert bool(jnp.all(r.experts >= 0)) and bool(
        jnp.all(r.experts < mcfg.num_experts))
    np.testing.assert_allclose(np.asarray(r.weights.sum(-1)), 1.0,
                               rtol=1e-5)
    # top-k experts are distinct per token
    assert bool(jnp.all(r.experts[:, 0] != r.experts[:, 1]))


def test_routing_without_renormalization():
    """norm_topk_prob=False (DeepSeek-V2) combines the chosen experts with
    their softmax probabilities as they are; the default renormalizes the
    same experts' weights to sum to one."""
    mcfg, params = mk(E=8, top_k=3)
    raw = dataclasses.replace(mcfg, norm_topk_prob=False)
    x = jax.random.normal(KEY, (16, 32), jnp.float32)
    r = moe_lib.route_topk(params["router"], x, raw)
    n = moe_lib.route_topk(params["router"], x, mcfg)
    np.testing.assert_array_equal(np.asarray(r.experts),
                                  np.asarray(n.experts))
    np.testing.assert_allclose(
        np.asarray(r.weights),
        np.take_along_axis(np.asarray(r.probs), np.asarray(r.experts), -1),
        rtol=1e-6)
    assert float(r.weights.sum(-1).max()) < 1.0
    np.testing.assert_allclose(
        np.asarray(n.weights),
        np.asarray(r.weights / r.weights.sum(-1, keepdims=True)), rtol=1e-5)


def test_padded_experts_receive_no_tokens():
    mcfg, _ = mk(E=6)
    params = moe_lib.moe_init(KEY, 32, mcfg, num_experts_padded=8)
    x = jax.random.normal(KEY, (128, 32), jnp.float32)
    r = moe_lib.route_topk(params["router"], x, mcfg, num_experts_padded=8)
    assert bool(jnp.all(r.experts < 6))
    assert float(r.probs[:, 6:].max()) < 1e-20


def test_capacity_equals_dense_when_no_drops():
    mcfg, params = mk(cf=16.0)
    x = jax.random.normal(KEY, (4, 16, 32), jnp.float32)
    y_d, _ = moe_lib.moe_apply_dense(params, x, mcfg)
    y_c, _ = moe_lib.moe_apply_capacity(params, x, mcfg)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_d), atol=1e-5)


def test_capacity_drops_with_tiny_capacity():
    mcfg, params = mk(cf=16.0)
    x = jax.random.normal(KEY, (1, 64, 32), jnp.float32)
    y_full, _ = moe_lib.moe_apply_capacity(params, x, mcfg)
    y_tiny, _ = moe_lib.moe_apply_capacity(params, x, mcfg, capacity=1)
    # with capacity=1 most tokens are dropped => outputs differ
    assert float(jnp.max(jnp.abs(y_full - y_tiny))) > 1e-3


def test_shared_expert_added():
    mcfg, params = mk(n_shared=2)
    x = jax.random.normal(KEY, (2, 8, 32), jnp.float32)
    y, _ = moe_lib.moe_apply_dense(params, x, mcfg)
    params_no = {k: v for k, v in params.items() if k != "shared"}
    y_no, _ = moe_lib.moe_apply_dense(params_no, x, mcfg)
    shared = moe_lib.shared_expert_apply(params, x.reshape(-1, 32))
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(y_no + shared.reshape(y.shape)),
                               atol=1e-5)


def test_load_balance_loss_uniform_router_near_one():
    """For a (near-)uniform router the Switch aux loss approaches 1."""
    mcfg = MoEConfig(num_experts=16, top_k=2, expert_ffn_dim=8)
    T = 8192
    probs = jnp.full((T, 16), 1.0 / 16)
    experts = jax.random.randint(KEY, (T, 2), 0, 16)
    r = moe_lib.Routing(weights=jnp.full((T, 2), 0.5), experts=experts,
                        probs=probs)
    val = float(moe_lib.load_balance_loss(r, mcfg))
    assert abs(val - 1.0) < 0.05, val


@given(T=st.integers(2, 64), E=st.sampled_from([4, 8]),
       top_k=st.integers(1, 3))
@settings(max_examples=25, deadline=None)
def test_dispatch_combine_identity_property(T, E, top_k):
    """With identity experts and no drops, combine(dispatch(x)) == x
    (routing weights sum to 1)."""
    mcfg = MoEConfig(num_experts=E, top_k=top_k, expert_ffn_dim=8,
                     capacity_factor=float(E))
    params = moe_lib.moe_init(KEY, 16, mcfg)
    x = jax.random.normal(jax.random.PRNGKey(T), (T, 16), jnp.float32)
    cap = moe_lib.expert_capacity(T, mcfg)
    info = moe_lib.moe_dispatch(params, x, mcfg, capacity=cap)
    y = moe_lib.moe_combine(info, info.buffers, T, x.dtype)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-5)


def test_expert_capacity_multiple_of():
    mcfg = MoEConfig(num_experts=8, top_k=2, expert_ffn_dim=8,
                     capacity_factor=1.25)
    cap = moe_lib.expert_capacity(100, mcfg, multiple_of=4)
    assert cap % 4 == 0
    assert cap >= 100 * 2 / 8 * 1.25
