"""jit'd public wrapper for the grouped expert FFN kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.moe_gemm.kernel import moe_gemm_pallas
from repro.kernels.moe_gemm.ref import moe_gemm_ref


@partial(jax.jit, static_argnames=("bc", "bh", "use_kernel"))
def moe_gemm(x, w_gate, w_up, w_down, bc: int = 128, bh: int = 512,
             use_kernel: bool = True):
    """Grouped expert SwiGLU FFN. Falls back to the jnp reference when the
    shapes do not tile (ragged C/H)."""
    E, C, M = x.shape
    H = w_gate.shape[-1]
    bc_ = min(bc, C)
    bh_ = min(bh, H)
    if not use_kernel or C % bc_ or H % bh_:
        return moe_gemm_ref(x, w_gate, w_up, w_down)
    return moe_gemm_pallas(x, w_gate, w_up, w_down, bc=bc_, bh=bh_)
