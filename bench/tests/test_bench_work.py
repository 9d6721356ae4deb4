"""The work counters kept with each configuration, against measured and
hand-worked figures."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(BENCH, "models"))

import moe_transformer as mt  # noqa: E402


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_qwen_weight_bytes_match_the_served_weights():
    # the bf16 weights ServingEngine held on a v5e chip at 6 layers
    assert mt.weight_bytes(_cfg("qwen2-moe-a2.7b.6l"), 2) == 8_091_365_376


def test_qwen_token_flops_by_hand():
    cfg = _cfg("qwen2-moe-a2.7b.6l")
    # per layer: q, k, v and o 2048x2048, router 2048x60, 4 routed experts
    # of 3 x 2048 x 1408, the shared expert 3 x 2048 x 5632; head
    # 2048 x 151936
    per_layer = (4 * 2048 * 2048 + 2048 * 60 + 4 * 3 * 2048 * 1408
                 + 3 * 2048 * 5632)
    assert mt.token_flops(cfg) == 2 * (6 * per_layer + 2048 * 151936)
    assert mt.token_flops(cfg, head=False) == 2 * 6 * per_layer
    # a query over 3000 keys: QK and PV, 16 heads of 128, 6 layers
    assert mt.attention_flops(cfg, 3000) == 4 * 6 * 16 * 128 * 3000


def test_qwen_decode_attention_work_by_hand():
    cfg = _cfg("qwen2-moe-a2.7b.6l")
    flops, nbytes = mt.decode_attention_work(cfg, [200, 1800], 2)
    assert flops == 4 * 16 * 128 * 2000
    # K and V of 16 heads x 128 per key, plus q and out of 16 x 128 a row
    assert nbytes == 2 * (2 * 16 * 128 * 2000 + 2 * 2 * 16 * 128)


def test_departures_are_what_is_served():
    """The file keeps the source's values; the reference computes the
    served value of each stated departure."""
    cfg = _cfg("qwen2-moe-a2.7b.6l")
    assert cfg["norm_topk_prob"] is False
    s = mt.Shapes(cfg)
    assert s.norm_topk is True and s.shared_gate is False
    assert dict(mt._shape_key(cfg))["norm_topk_prob"] is True


@pytest.mark.parametrize("name", ["qwen2-moe-a2.7b.6l"])
def test_params_follow_the_file(name):
    cfg = _cfg(name)
    shapes = mt.param_shapes(cfg)
    s = mt.Shapes(cfg)
    assert len([p for p in shapes if p[0] == "layers" and
                p[2:] == ("moe", "experts", "gate")]) == s.L
    assert shapes[("layers", 0, "moe", "experts", "down")][0] == \
        (s.E, s.F, s.M)
    assert (("lm_head", "kernel") in shapes) == (not s.tied)


def test_warm_up_covers_every_prefill_group_of_the_chat_mix():
    """One row a group (a token budget of one prompt a step), one group
    per padded length that the chat mix's prompts of 16 to 1536 tokens
    prefill to, each warmed at the shortest prompt that reaches it."""
    import importlib.util
    sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
    from repro.sched.occupancy import bucket_length
    path = os.path.join(BENCH, "systems", "moe_transformer.py")
    system = importlib.util.module_from_spec(
        importlib.util.spec_from_file_location("bench_system_test", path))
    system.__spec__.loader.exec_module(system)
    cfg = _cfg("qwen2-moe-a2.7b.6l")
    groups = system.prefill_groups(cfg, 16, 1536)
    assert all(rows == 1 for _, rows, _ in groups)
    want = sorted({bucket_length(n - 1) for n in range(16, 1537)})
    assert [b for b, _, _ in groups] == want
    for b, _, prompt in groups:
        assert bucket_length(prompt - 1) == b
        assert prompt == 16 or bucket_length(prompt - 2) < b


@pytest.mark.parametrize("dtype", ["float8_e4m3fn", "float8_e5m2"])
def test_control_rounding_is_the_type_conversion(dtype):
    """The precision control's rounding, done in arithmetic, gives bit
    for bit what a conversion to the narrow type gives: normals,
    subnormals, ties and values that carry into the next power of two."""
    import jax.numpy as jnp
    import numpy as np
    dt = jnp.dtype(dtype)
    fi = jnp.finfo(dt)
    rng = np.random.default_rng(3)
    x = np.concatenate([
        rng.normal(0, 0.03, 20000),                  # weights' scale
        rng.normal(0, float(fi.smallest_normal), 5000),   # subnormals
        rng.uniform(-float(fi.max), float(fi.max), 2000),
        [0.0, -0.0, float(fi.max), -float(fi.max),
         float(fi.smallest_normal), 0.5 * float(fi.smallest_normal)],
    ]).astype(np.float32)
    grid = np.asarray(jnp.asarray(x).astype(dt).astype(jnp.float32))
    # ties between neighbours of the type's grid round to even
    ties = ((grid[:-1] + grid[1:]) / 2).astype(np.float32)
    x = np.concatenate([x, ties])
    want = np.asarray(jnp.asarray(x).astype(dt).astype(jnp.float32))
    got = np.asarray(mt.round_to(jnp.asarray(x), dt))
    assert np.array_equal(got.view(np.uint32) & 0x7FFFFFFF,
                          want.view(np.uint32) & 0x7FFFFFFF)
    assert np.array_equal(np.signbit(got[got != 0]),
                          np.signbit(want[got != 0]))
