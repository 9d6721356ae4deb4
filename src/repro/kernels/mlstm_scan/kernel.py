"""Pallas TPU kernel: mLSTM matrix-memory recurrence scan.

THE memory hot-spot of xLSTM on TPU: the naive lax.scan round-trips the
per-head state C in R^{DxD} through HBM every timestep —
2 * 4B * B*H*D^2 * S bytes (for xlstm-1.3b at 32k prefill that is ~100+
seconds of HBM time per device; see EXPERIMENTS.md §Perf pair C).

Here grid = (B, H, S // bs) with the (C, n, m) state resident in VMEM
scratch across the (innermost) sequence-chunk steps: HBM traffic is one
read of q/k/v/gates and one write of h — the operational minimum. For
D = 1024 the state is 4 MB f32, comfortably inside the 16 MB VMEM budget
with the [bs, D] streaming blocks.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _mlstm_kernel(q_ref, k_ref, v_ref, i_ref, f_ref, cin_ref, nin_ref,
                  min_ref, h_ref, cout_ref, nout_ref, mout_ref,
                  C_ref, n_ref, m_ref, *, bs: int, n_s_steps: int):
    s_step = pl.program_id(2)

    @pl.when(s_step == 0)
    def _init():
        C_ref[...] = cin_ref[0, 0].astype(jnp.float32)
        n_ref[...] = nin_ref[0, 0].astype(jnp.float32)
        m_ref[...] = min_ref[0, 0].astype(jnp.float32)

    q = q_ref[0, 0].astype(jnp.float32)      # [bs, D]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    ig = i_ref[0, 0].astype(jnp.float32)     # [bs]
    lf = f_ref[0, 0].astype(jnp.float32)

    def body(t, carry):
        C, n, m = carry
        m_new = jnp.maximum(lf[t] + m, ig[t])
        f_p = jnp.exp(lf[t] + m - m_new)
        i_p = jnp.exp(ig[t] - m_new)
        n_new = f_p * n + i_p * k[t]
        C_new = f_p * C + (i_p * v[t])[:, None] * k[t][None, :]
        denom = jnp.maximum(jnp.abs(jnp.sum(n_new * q[t])), 1.0)
        h_ref[0, 0, t, :] = (C_new @ q[t] / denom).astype(h_ref.dtype)
        return C_new, n_new, m_new

    C, n, m = jax.lax.fori_loop(
        0, bs, body, (C_ref[...], n_ref[...], m_ref[...]))
    C_ref[...] = C
    n_ref[...] = n
    m_ref[...] = m

    @pl.when(s_step == n_s_steps - 1)
    def _flush():
        cout_ref[0, 0] = C.astype(cout_ref.dtype)
        nout_ref[0, 0] = n.astype(nout_ref.dtype)
        mout_ref[0, 0] = m.astype(mout_ref.dtype)


def mlstm_scan_pallas(q, k, v, i_gate, log_f, C0, n0, m0, *, bs: int = 128,
                      interpret: Optional[bool] = None):
    """q,k,v: [B,H,S,D]; i_gate/log_f: [B,H,S]; C0: [B,H,D,D];
    n0: [B,H,D]; m0: [B,H]. Returns (h [B,H,S,D], C, n, m)."""
    B, H, S, D = q.shape
    bs = min(bs, S)
    assert S % bs == 0, (S, bs)
    n_s = S // bs
    m0e = m0[..., None]                       # [B,H,1] (2D-min blocks)

    kernel = functools.partial(_mlstm_kernel, bs=bs, n_s_steps=n_s)
    h, C, n, m = pl.pallas_call(
        kernel,
        grid=(B, H, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, bs, D), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, bs, D), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, bs, D), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, bs), lambda b, h, s: (b, h, s)),
            pl.BlockSpec((1, 1, bs), lambda b, h, s: (b, h, s)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, D), lambda b, h, s: (b, h, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, h, s: (b, h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bs, D), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, D), lambda b, h, s: (b, h, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, h, s: (b, h, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, D, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((D, D), jnp.float32),
            pltpu.VMEM((D,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v, i_gate, log_f, C0, n0, m0e)
    return h, C, n, m[..., 0]
