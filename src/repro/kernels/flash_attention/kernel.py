"""Pallas TPU kernel: causal GQA flash attention (prefill hot-spot).

Grid = (B * H, Sq // bq, Sk // bk), k-blocks innermost; the online-softmax
state (m, l) and the f32 output accumulator live in VMEM scratch across the
k-block steps. The KV block index map folds GQA: head h reads KV head
h // (H // Kv). Causal masking is positional inside the block; with
block-aligned shapes the MXU sees (bq x D) @ (D x bk) and (bq x bk) @
(bk x D), all dims multiples of 128 for bq = bk = 128, D = 128.

VMEM per step (bq=bk=128, D=128, bf16): q 32KB + k/v 64KB + acc f32 64KB +
m/l 1KB -> well under budget; larger bq/bk trade VMEM for fewer grid
steps.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def flash_kv_index_map(H: int, Kv: int):
    """The K/V ``index_map`` over the flattened (B*H, n_q, n_k) grid:
    program bh covers batch bh // H, head bh % H, and folds GQA — head h
    reads KV row h // (H // Kv) of the flattened [B*Kv, S, D] operand.
    Module-level so ``repro.analysis.kernelcheck`` can evaluate it over
    the full grid with concrete integers."""
    g = H // Kv

    def kv_index(bh, qi, ki):
        return ((bh // H) * Kv + (bh % H) // g, ki, 0)
    return kv_index


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  n_k_steps: int, bq: int, bk: int, causal: bool,
                  window, scale: float):
    q_step = pl.program_id(1)
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                     # [bq, D]
    k = k_ref[0]                                     # [bk, D]
    v = v_ref[0]
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [bq, bk]

    q_pos = q_step * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_step * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(-1))
    p = jnp.exp(logits - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1)
    m_ref[...] = m_new
    acc_ref[...] = (acc_ref[...] * corr[:, None]
                    + jax.lax.dot_general(
                        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))

    @pl.when(k_step == n_k_steps - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window=None,
                           bq: int = 128, bk: int = 128,
                           interpret: Optional[bool] = None):
    """q: [B,S,H,D]; k/v: [B,S,Kv,D] -> [B,S,H,D]."""
    B, S, H, D = q.shape
    Kv = k.shape[2]
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    n_q, n_k = S // bq, S // bk

    # flatten (B, H): program bh -> b = bh // H, h = bh % H, kv = h // g
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Kv, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Kv, S, D)

    kv_index = flash_kv_index_map(H, Kv)

    kernel = functools.partial(
        _flash_kernel, n_k_steps=n_k, bq=bq, bk=bk, causal=causal,
        window=window, scale=1.0 / math.sqrt(D))
    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, D), kv_index),
            pl.BlockSpec((1, bk, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(qf, kf, vf)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
