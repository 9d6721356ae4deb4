"""jit'd public wrapper for the mLSTM recurrence kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.mlstm_scan.kernel import mlstm_scan_pallas
from repro.kernels.mlstm_scan.ref import mlstm_scan_ref


@partial(jax.jit, static_argnames=("bs", "use_kernel"))
def mlstm_scan(q, k, v, i_gate, log_f, C0, n0, m0, bs: int = 128,
               use_kernel: bool = True):
    S = q.shape[2]
    bs_ = min(bs, S)
    if not use_kernel or S % bs_:
        return mlstm_scan_ref(q, k, v, i_gate, log_f, C0, n0, m0)
    return mlstm_scan_pallas(q, k, v, i_gate, log_f, C0, n0, m0, bs=bs_)
