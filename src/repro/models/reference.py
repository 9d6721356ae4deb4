"""Plain float32 reference forward for the decoder-only GQA models (dense
and MoE families, full causal attention).

Written from the layer equations and independent of the serving stack: no
kernels, no KV cache, no batching, no capacity buffers, no expert
parallelism. One unbatched sequence in, float32 logits for every position
out. Matrix products run under ``default_matmul_precision("highest")``,
since a TPU otherwise multiplies float32 in bfloat16 passes. Weights of any
dtype are upcast to float32.

The equations are those of ``repro.models`` (pre-norm RMSNorm blocks,
rotate-half RoPE, SwiGLU experts, softmax router with the top-k weights
renormalized to sum to one, shared experts added ungated). Two departures
from the published Qwen1.5-MoE follow from that and are shared with the
served model: the published model gates its shared experts with a
sigmoid (``shared_expert_gate``) and does not renormalize the top-k
weights (``norm_topk_prob=false``).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _linear(p, x):
    y = x @ p["kernel"]
    return y + p["bias"] if "bias" in p else y


def _rmsnorm(p, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def _rope(x, pos, theta):
    """x: [S, heads, D]; rotate-half RoPE at absolute positions ``pos``."""
    D = x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float32) / D)
    ang = pos[:, None, None].astype(jnp.float32) * freqs      # [S, 1, D/2]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _attention(p, cfg: ModelConfig, h):
    S = h.shape[0]
    H, Kv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pos = jnp.arange(S)
    q = _rope(_linear(p["wq"], h).reshape(S, H, D), pos, cfg.rope_theta)
    k = _rope(_linear(p["wk"], h).reshape(S, Kv, D), pos, cfg.rope_theta)
    v = _linear(p["wv"], h).reshape(S, Kv, D)
    k = jnp.repeat(k, H // Kv, axis=1)                 # GQA: head i reads
    v = jnp.repeat(v, H // Kv, axis=1)                 # KV head i // g
    logits = jnp.einsum("shd,thd->hst", q, k) / math.sqrt(D)
    causal = pos[:, None] >= pos[None, :]
    probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    out = jnp.einsum("hst,thd->shd", probs, v).reshape(S, H * D)
    return _linear(p["wo"], out)


def _swiglu(p, x):
    return _linear(p["down"], jax.nn.silu(_linear(p["gate"], x))
                   * _linear(p["up"], x))


def _moe(p, cfg: ModelConfig, h):
    """Every token through its top-k experts, exactly (no capacity)."""
    m = cfg.moe
    probs = jax.nn.softmax(_linear(p["router"], h)[:, :m.num_experts], -1)
    w, idx = jax.lax.top_k(probs, m.top_k)
    w = w / w.sum(-1, keepdims=True)
    T = h.shape[0]
    combine = jnp.zeros((T, m.num_experts)).at[
        jnp.arange(T)[:, None], idx].add(w)                    # [T, E]
    ex = jax.tree.map(lambda a: a[:m.num_experts], p["experts"])
    act = (jax.nn.silu(jnp.einsum("tm,emh->teh", h, ex["gate"]))
           * jnp.einsum("tm,emh->teh", h, ex["up"]))
    y = jnp.einsum("teh,ehm->tm", act * combine[:, :, None], ex["down"])
    if "shared" in p:
        y = y + _swiglu(p["shared"], h)
    return y


@partial(jax.jit, static_argnums=(1,))
def _block(p, cfg: ModelConfig, x):
    p = _f32(p)
    x = x + _attention(p["attn"], cfg, _rmsnorm(p["ln1"], x, cfg.norm_eps))
    h = _rmsnorm(p["ln2"], x, cfg.norm_eps)
    return x + (_moe(p["moe"], cfg, h) if "moe" in p else _swiglu(p["mlp"], h))


@jax.jit
def _embed(table, tokens):
    return jnp.asarray(table[tokens], jnp.float32)


@partial(jax.jit, static_argnums=(3,))
def _head(norm, w, x, eps: float):
    return _rmsnorm(_f32(norm), x, eps) @ jnp.asarray(w, jnp.float32)


def reference_logits(params, cfg: ModelConfig, tokens,
                     last_only: bool = False):
    """Float32 logits [S, V] of one unbatched token sequence ([1, V] of
    its last position with ``last_only``). One jit per layer, so at most
    one layer's float32 weights are live at a time."""
    if cfg.family not in ("dense", "moe") or cfg.attention != "full" \
            or cfg.mla_kv_lora_rank:
        raise ValueError(f"no plain reference for {cfg.name} "
                         f"(family={cfg.family!r}, "
                         f"attention={cfg.attention!r})")
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"]["embedding"],
                   jnp.asarray(tokens, jnp.int32))
        for layer in params["layers"]:
            x = _block(layer, cfg, x)
        w = (params["embed"]["embedding"].T if cfg.tie_embeddings
             else params["lm_head"]["kernel"])
        if last_only:
            x = x[-1:]
        return _head(params["final_norm"], w, x, cfg.norm_eps)


def relative_error(got, want) -> float:
    """||got - want|| / ||want|| over all entries (float64 on the host)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))
