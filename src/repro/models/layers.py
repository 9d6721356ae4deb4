"""Primitive layers (pure JAX, pytree-of-arrays parameters).

Parameters are nested dicts of jnp arrays. Every layer provides
``init_*(key, ...) -> params`` and a pure apply function. Weights are
created in float32; apply functions cast them to the compute dtype, which
is free when they are already held in it (the serving engine holds its
weights in its serving dtype).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def _normal(key, shape, scale):
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale)


def dense_init(key, in_dim: int, out_dim: int, bias: bool = False,
               scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    p = {"kernel": _normal(key, (in_dim, out_dim), scale)}
    if bias:
        p["bias"] = jnp.zeros((out_dim,), dtype=jnp.float32)
    return p


def dense_apply(p, x):
    y = x @ p["kernel"].astype(x.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


def embedding_init(key, vocab: int, dim: int):
    return {"embedding": _normal(key, (vocab, dim), 1.0 / math.sqrt(dim))}


def embedding_apply(p, tokens, dtype=jnp.bfloat16):
    return jnp.take(p["embedding"].astype(dtype), tokens, axis=0)


def embedding_attend(p, x):
    """Tied readout: logits = x @ E^T, accumulated in float32."""
    e = p["embedding"]
    return jnp.dot(x.astype(e.dtype), e.T,
                   preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def rmsnorm_init(dim: int):
    return {"scale": jnp.ones((dim,), dtype=jnp.float32)}


def rmsnorm_apply(p, x, eps: float = 1e-5):
    dtype = x.dtype
    x = jnp.asarray(x, jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * p["scale"]
    return y.astype(dtype)


def layernorm_init(dim: int):
    return {"scale": jnp.ones((dim,), dtype=jnp.float32),
            "bias": jnp.zeros((dim,), dtype=jnp.float32)}


def layernorm_apply(p, x, eps: float = 1e-5):
    dtype = x.dtype
    x = jnp.asarray(x, jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction for a context stretched ``factor``
    times (DeepSeek-V2's ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_ramp(half: int, head_dim: int, theta: float, scaling):
    """Per-frequency share of the interpolated frequency: 0 below the
    dimension that turns ``beta_fast`` times in the original context, 1
    above the one that turns ``beta_slow`` times, linear between."""
    def dim_at(rotations):
        return (head_dim * math.log(scaling.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim_at(scaling.beta_fast)), 0)
    high = min(math.ceil(dim_at(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = (jnp.arange(half, dtype=jnp.float32) - low) / (high - low)
    return jnp.clip(ramp, 0.0, 1.0)


def rope_frequencies(head_dim: int, theta: float = 10000.0, scaling=None):
    """Inverse frequencies [hd/2]; with a ``YarnScaling``, YaRN's blend of
    the original and the ``factor``-interpolated frequencies."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    freqs = 1.0 / (theta ** exponents)                      # [hd/2]
    if scaling is None:
        return freqs
    ramp = _yarn_ramp(head_dim // 2, head_dim, theta, scaling)
    return freqs / scaling.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x, positions, theta: float = 10000.0, scaling=None):
    """x: [..., seq, heads, head_dim]; positions: [..., seq]. Rotate-half
    pairs; ``scaling`` (``YarnScaling``) takes YaRN's frequencies and
    scales cos and sin by mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta, scaling)           # [hd/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [...,S,hd/2]
    angles = angles[..., None, :]                          # [...,S,1,hd/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# SwiGLU MLP (gate/up/down; paper Eq. 2 structure)
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, ffn_dim: int):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": dense_init(k1, d_model, ffn_dim),
        "up": dense_init(k2, d_model, ffn_dim),
        "down": dense_init(k3, ffn_dim, d_model),
    }


def mlp_apply(p, x):
    g = dense_apply(p["gate"], x)
    u = dense_apply(p["up"], x)
    return dense_apply(p["down"], jax.nn.silu(g) * u)


# ---------------------------------------------------------------------------
# causal depthwise conv1d (used by xLSTM / RecurrentGemma blocks)
# ---------------------------------------------------------------------------

def conv1d_init(key, dim: int, width: int):
    return {"kernel": _normal(key, (width, dim), 1.0 / math.sqrt(width)),
            "bias": jnp.zeros((dim,), dtype=jnp.float32)}


def conv1d_apply(p, x, state=None):
    """Causal depthwise conv. x: [B, S, D]. ``state``: [B, width-1, D] tail
    of the previous segment (decode); returns (y, new_state). Compute runs
    in x.dtype; new_state keeps the incoming state's dtype (scan-carry
    stability)."""
    w = p["kernel"].astype(x.dtype)                        # [W, D]
    width = w.shape[0]
    if state is None:
        state = jnp.zeros(x.shape[:-2] + (width - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([state.astype(x.dtype), x], axis=-2)
    ys = sum(xp[..., i:i + x.shape[-2], :] * w[i] for i in range(width))
    new_state = (xp[..., -(width - 1):, :].astype(state.dtype)
                 if width > 1 else state)
    return ys + p["bias"].astype(x.dtype), new_state
