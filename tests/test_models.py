"""Model substrate: decode==forward consistency, scan==loop, chunked CE,
flash==sdpa, across families."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ShapeConfig
from repro.models import build_model, frontend_shape
from repro.models.transformer import ExecutionContext, chunked_softmax_xent

KEY = jax.random.PRNGKey(0)
B, S = 2, 24


def _setup(arch, **model_kw):
    cfg = get_smoke_config(arch)
    ctx = ExecutionContext(moe_impl="dense")
    model = build_model(cfg, ctx=ctx, dtype=jnp.float32, **model_kw)
    params = model.init(KEY)
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    fs = frontend_shape(cfg, ShapeConfig("t", S, B, "t"))
    extra = jax.random.normal(KEY, fs, jnp.float32) if fs else None
    return cfg, model, params, tokens, extra


@pytest.mark.parametrize("arch", [
    "qwen2-1.5b", "starcoder2-3b", "xlstm-1.3b", "recurrentgemma-9b",
    "deepseek-v2-lite", "qwen2-moe-a2.7b", "internvl2-1b",
    "seamless-m4t-large-v2", "granite-moe-1b-a400m",
])
def test_decode_matches_forward(arch):
    """Teacher-forced decode logits == full-forward logits (exact caches)."""
    cfg, model, params, tokens, extra = _setup(arch)
    memory = model.encode(params, extra) if cfg.is_encoder_decoder else None
    ee = None if cfg.is_encoder_decoder else extra
    logits_full, _, _ = model.forward(params, tokens, extra_embeds=ee,
                                      memory=memory)
    half = S // 2
    lg, caches = model.prefill(params, tokens[:, :half], extra_embeds=ee,
                               memory=memory, seq_budget=S)
    off = (extra.shape[1] if (ee is not None and cfg.family == "vlm") else 0)
    errs = [float(jnp.max(jnp.abs(lg[:, -1] - logits_full[:, half - 1 + off])))]
    for t in range(half, S):
        lg, caches = model.decode_step(params, tokens[:, t:t + 1], caches,
                                       memory=memory)
        errs.append(float(jnp.max(jnp.abs(lg[:, 0] - logits_full[:, t + off]))))
    assert max(errs) < 1e-4, (arch, max(errs))


@pytest.mark.parametrize("last", [False, True],
                         ids=["bucket_end", "last_positions"])
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "qwen2-moe-a2.7b",
                                  "internvl2-1b"])
def test_prefill_head_matches_forward_rows(arch, last):
    """``prefill`` applies the output head to the returned rows only; its
    logits are the full-sequence head's [B, S, V] logits at those rows
    (image slots included in the positions of a vlm)."""
    cfg, model, params, tokens, extra = _setup(arch)
    full, _, _ = model.forward(params, tokens, extra_embeds=extra)
    off = extra.shape[1] if extra is not None else 0
    pos = np.asarray([5, S - 3]) if last else None
    lg, _ = model.prefill(params, tokens, extra_embeds=extra,
                          last_positions=pos)
    rows = (pos if last else np.full(B, S - 1)) + off
    want = full[jnp.arange(B), rows]
    assert lg.shape == (B, 1, full.shape[-1])
    # the head multiplies B rows where forward multiplies B x S; the CPU
    # may block the two products differently, so the float32 sums agree
    # to rounding, not always bit for bit (equal on the text configs
    # here, 2.5e-6 apart on the vlm); a wrong row is ~1 off
    np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(want),
                               rtol=0, atol=1e-5)


def test_sliding_window_ring_cache_decode():
    cfg = dataclasses.replace(get_smoke_config("qwen2-1.5b"),
                              attention="sliding", sliding_window=8)
    model = build_model(cfg, dtype=jnp.float32)
    params = model.init(KEY)
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    logits_full, _, _ = model.forward(params, tokens)
    half = S // 2
    lg, caches = model.prefill(params, tokens[:, :half], seq_budget=S)
    errs = []
    for t in range(half, S):
        lg, caches = model.decode_step(params, tokens[:, t:t + 1], caches)
        errs.append(float(jnp.max(jnp.abs(lg[:, 0] - logits_full[:, t]))))
    assert max(errs) < 1e-4, max(errs)


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "qwen2-moe-a2.7b",
                                  "xlstm-1.3b", "recurrentgemma-9b"])
def test_scan_layers_equals_loop(arch):
    cfg = get_smoke_config(arch)
    m_loop = build_model(cfg, dtype=jnp.float32)
    m_scan = build_model(cfg, scan_layers=True, dtype=jnp.float32)
    p_loop = m_loop.init(KEY)
    gsize = len(m_scan.group)
    stacked = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[p_loop["layers"][g * gsize:(g + 1) * gsize]
          for g in range(m_scan.num_groups)])
    p_scan = {k: v for k, v in p_loop.items() if k != "layers"}
    p_scan["layer_groups"] = stacked
    tokens = jax.random.randint(KEY, (B, 16), 0, cfg.vocab_size)
    l1, _, a1 = m_loop.forward(p_loop, tokens)
    l2, _, a2 = m_scan.forward(p_scan, tokens)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=2e-5)
    assert float(abs(a1 - a2)) < 1e-6


@pytest.mark.parametrize("arch,scan", [
    ("qwen2-moe-a2.7b", False), ("qwen2-moe-a2.7b", True),
    ("internvl2-1b", False), ("seamless-m4t-large-v2", False),
    ("recurrentgemma-9b", True)])
def test_init_in_dtype_casts_float32_init(arch, scan):
    """``init(key, dtype=...)`` (built layer by layer under jit) holds
    the float32 initializer's values in ``dtype``: within bf16 rounding,
    2^-8 relative where jit scales a float32 value one ulp apart."""
    model = build_model(get_smoke_config(arch), scan_layers=scan,
                        dtype=jnp.float32)
    ref = model.init(KEY)
    got = model.init(KEY, dtype=jnp.bfloat16)
    assert jax.tree.structure(got) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                   rtol=2 ** -8, atol=0)


def test_chunked_ce_equals_naive():
    cfg, model, params, tokens, _ = _setup("qwen2-1.5b")
    for chunk in (4, 8, 23, 64):
        loss_c = model.loss(params, tokens, ce_chunk=chunk)
        logits, _, _ = model.forward(params, tokens)
        lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
        naive = -jnp.take_along_axis(lp, tokens[:, 1:][..., None],
                                     -1).mean()
        assert float(abs(loss_c - naive)) < 1e-5, chunk


def test_chunked_ce_grads_match():
    cfg, model, params, tokens, _ = _setup("qwen2-1.5b")
    g1 = jax.grad(lambda p: model.loss(p, tokens, ce_chunk=8))(params)
    g2 = jax.grad(lambda p: model.loss(p, tokens, ce_chunk=1024))(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_impl_matches_xla():
    cfg = get_smoke_config("qwen2-1.5b")
    m1 = build_model(cfg, ctx=ExecutionContext(attn_impl="xla"),
                     dtype=jnp.float32)
    m2 = build_model(cfg, ctx=ExecutionContext(attn_impl="flash"),
                     dtype=jnp.float32)
    p = m1.init(KEY)
    tok = jax.random.randint(KEY, (2, 128), 0, cfg.vocab_size)
    l1, _, _ = m1.forward(p, tok)
    l2, _, _ = m2.forward(p, tok)
    assert float(jnp.max(jnp.abs(l1 - l2))) < 5e-5


def test_chunked_attention_matches_sdpa():
    from repro.models.attention import (_causal_mask, _flash_sdpa_xla,
                                        _sdpa)
    ks = jax.random.split(KEY, 3)
    Bs, Ss, H, Kv, D = 2, 200, 8, 2, 32
    q = jax.random.normal(ks[0], (Bs, Ss, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (Bs, Ss, Kv, D), jnp.float32)
    v = jax.random.normal(ks[2], (Bs, Ss, Kv, D), jnp.float32)
    pos = jnp.arange(Ss)
    for win in (None, 37):
        ref = _sdpa(q, k, v, _causal_mask(pos, pos, win))
        out = _flash_sdpa_xla(q, k, v, pos, pos, win, q_chunk=64,
                              k_chunk=48)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_rglru_kernel_path_matches_scan():
    from repro.models import rglru as rl
    cfg = get_smoke_config("recurrentgemma-9b")
    p = rl.rglru_init(KEY, cfg)
    x = jax.random.normal(KEY, (2, 128, cfg.d_model), jnp.float32)
    y1, s1 = rl.rglru_apply(p, cfg, x)
    y2, s2 = rl.rglru_apply(p, cfg, x, use_kernel=True)
    assert float(jnp.max(jnp.abs(y1 - y2))) < 1e-5
    assert float(jnp.max(jnp.abs(s1["h"] - s2["h"]))) < 1e-5
