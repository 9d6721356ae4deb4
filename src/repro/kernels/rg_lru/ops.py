"""jit'd public wrapper for the RG-LRU scan kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.rg_lru.kernel import rg_lru_scan_pallas
from repro.kernels.rg_lru.ref import rg_lru_scan_ref


@partial(jax.jit, static_argnames=("bs", "bw", "use_kernel"))
def rg_lru_scan(a, b, h0, bs: int = 256, bw: int = 512,
                use_kernel: bool = True):
    B, S, W = a.shape
    bs_, bw_ = min(bs, S), min(bw, W)
    if not use_kernel or S % bs_ or W % bw_:
        return rg_lru_scan_ref(a, b, h0)
    return rg_lru_scan_pallas(a, b, h0, bs=bs_, bw=bw_)
