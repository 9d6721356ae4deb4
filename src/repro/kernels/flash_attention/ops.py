"""jit'd public wrapper for the flash-attention kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref


@partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                   "use_kernel"))
def flash_attention(q, k, v, causal: bool = True, window=None,
                    bq: int = 128, bk: int = 128, use_kernel: bool = True):
    S = q.shape[1]
    bq_, bk_ = min(bq, S), min(bk, S)
    if not use_kernel or S % bq_ or S % bk_:
        return flash_attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  bq=bq_, bk=bk_)
