"""Pallas TPU kernel: grouped per-expert SwiGLU FFN (the EG compute of DEP).

Tiling (TPU-native, see DESIGN.md hardware-adaptation):
  grid = (E, C // bc, H // bh)   — experts outermost, token tiles, then
                                   hidden tiles innermost so the f32
                                   accumulator for the down-projection
                                   lives in VMEM scratch across bh steps.
  Per step the MXU sees (bc x M) @ (M x bh) twice (gate, up) and
  (bc x bh) @ (bh x M) once (down) — all dims multiples of 128.

VMEM footprint per step (bc=128, bh=512, M<=4096, bf16):
  x tile 128xM (1MB) + Wg/Wu Mxbh (4MB) + Wd bhxM (4MB) + acc 128xM f32
  (2MB) -> ~11MB, under the 16MB/core budget.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _ffn_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *,
                n_h_steps: int):
    h_step = pl.program_id(2)

    @pl.when(h_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                     # [bc, M]
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    act = (g * jax.lax.logistic(g)) * u              # silu(g) * u, f32
    acc_ref[...] += jnp.dot(act.astype(x.dtype), wd_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(h_step == n_h_steps - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def moe_gemm_pallas(x, w_gate, w_up, w_down, *, bc: int = 128,
                    bh: int = 512, interpret: Optional[bool] = None):
    """x: [E, C, M]; w_gate/w_up: [E, M, H]; w_down: [E, H, M] -> [E, C, M]."""
    E, C, M = x.shape
    H = w_gate.shape[-1]
    bc = min(bc, C)
    bh = min(bh, H)
    assert C % bc == 0 and H % bh == 0, (C, bc, H, bh)
    n_c, n_h = C // bc, H // bh
    grid = (E, n_c, n_h)

    kernel = functools.partial(_ffn_kernel, n_h_steps=n_h)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, M), lambda e, c, h: (e, c, 0)),
            pl.BlockSpec((1, M, bh), lambda e, c, h: (e, 0, h)),
            pl.BlockSpec((1, M, bh), lambda e, c, h: (e, 0, h)),
            pl.BlockSpec((1, bh, M), lambda e, c, h: (e, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, M), lambda e, c, h: (e, c, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, M), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, M), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(x, w_gate, w_up, w_down)
