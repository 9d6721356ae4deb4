"""jit'd public wrapper for the ragged decode-attention kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas, latent_decode_attention_pallas,
    paged_decode_attention_pallas)
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                latent_decode_attention_ref,
                                                paged_decode_attention_ref)


@partial(jax.jit, static_argnames=("bc", "use_kernel"))
def decode_attention(q, k_cache, v_cache, lengths, bc: int = 512,
                     use_kernel: bool = True):
    """q: [B,H,D]; k/v_cache: [B,Kv,C,D]; lengths: int [B] -> [B,H,D].

    A non-tiling ``bc`` shrinks to the largest tile-aligned block size
    dividing C (C=600 at bc=512 runs at bc=200); a cache with no such
    block raises ``ValueError``. ``use_kernel=False`` is the explicit
    oracle path."""
    if not use_kernel:
        return decode_attention_ref(q, k_cache, v_cache, lengths)
    return decode_attention_pallas(q, k_cache, v_cache, lengths, bc=bc)


@partial(jax.jit, static_argnames=("use_kernel",))
def decode_attention_paged(q, k_pages, v_pages, lengths, block_table,
                           use_kernel: bool = True):
    """Paged-KV decode attention: q [B,H,D]; k/v_pages [P,Kv,bs,D];
    lengths int [B]; block_table int [B,max_blocks] -> [B,H,D].

    The kernel's block size IS the page size, and a page block spans the
    pool's whole (bs, D) tail, so every page size tiles.
    ``use_kernel=False`` is the explicit gather-based oracle path."""
    if not use_kernel:
        return paged_decode_attention_ref(q, k_pages, v_pages, lengths,
                                          block_table)
    return paged_decode_attention_pallas(q, k_pages, v_pages, lengths,
                                         block_table)


@partial(jax.jit, static_argnames=("dv", "scale", "bc", "use_kernel"))
def decode_attention_latent(q, cache, lengths, *, dv: int, scale: float,
                            bc: int = 512, use_kernel: bool = True):
    """Absorbed-MLA decode attention: q [B,H,Dk]; cache [B,1,C,Dk] whose
    first ``dv`` columns are the values; lengths int [B] -> [B,H,dv].
    ``use_kernel=False`` is the explicit oracle path."""
    if not use_kernel:
        return latent_decode_attention_ref(q, cache, lengths, dv=dv,
                                           scale=scale)
    return latent_decode_attention_pallas(q, cache, lengths, dv=dv,
                                          scale=scale, bc=bc)
