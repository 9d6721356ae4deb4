"""Pallas TPU kernel: RG-LRU gated linear recurrence scan.

h_t = a_t * h_{t-1} + b_t over [B, S, W], elementwise in W — a pure
VPU workload. Grid = (B, W // bw, S // bs): the recurrence carry h lives in
VMEM scratch across the (innermost) sequence-chunk steps, so HBM traffic is
exactly one read of (a, b) and one write of h — the operational minimum —
instead of one state round-trip per timestep as in the naive scan.
Inside a chunk the loop over bs steps is a jax.lax.fori_loop on VMEM-
resident data (registers/VPU), which is what makes this kernel worth
having over lax.scan on TPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _rglru_kernel(a_ref, b_ref, h0_ref, o_ref, hlast_ref, carry_ref, *,
                  bs: int, n_s_steps: int):
    s_step = pl.program_id(2)

    @pl.when(s_step == 0)
    def _init():
        carry_ref[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)        # [bs, bw]
    b = b_ref[0].astype(jnp.float32)

    def body(t, carry):
        h = a[t] * carry + b[t]
        o_ref[0, t, :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bs, body, carry_ref[...])
    carry_ref[...] = h

    @pl.when(s_step == n_s_steps - 1)
    def _flush():
        hlast_ref[0] = h.astype(hlast_ref.dtype)


def rg_lru_scan_pallas(a, b, h0, *, bs: int = 256, bw: int = 512,
                       interpret: Optional[bool] = None):
    """a, b: [B, S, W]; h0: [B, W] -> (h [B, S, W], h_last [B, W])."""
    B, S, W = a.shape
    bs = min(bs, S)
    bw = min(bw, W)
    assert S % bs == 0 and W % bw == 0, (S, bs, W, bw)
    n_s, n_w = S // bs, W // bw

    kernel = functools.partial(_rglru_kernel, bs=bs, n_s_steps=n_s)
    h, h_last = pl.pallas_call(
        kernel,
        grid=(B, n_w, n_s),
        in_specs=[
            pl.BlockSpec((1, bs, bw), lambda bi, wi, si: (bi, si, wi)),
            pl.BlockSpec((1, bs, bw), lambda bi, wi, si: (bi, si, wi)),
            pl.BlockSpec((1, bw), lambda bi, wi, si: (bi, wi)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, bw), lambda bi, wi, si: (bi, si, wi)),
            pl.BlockSpec((1, bw), lambda bi, wi, si: (bi, wi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), jnp.float32),
            jax.ShapeDtypeStruct((B, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bw,), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(a, b, h0)
    return h, h_last
