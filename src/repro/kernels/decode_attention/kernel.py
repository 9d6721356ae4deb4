"""Pallas TPU kernel: single-token GQA decode attention over a ragged KV
cache.

Decode attention is HBM-bandwidth-bound: the cost of a step is the cache
bytes streamed. With continuous batching the cache is RAGGED — each slot
has its own context length — so streaming the full ``[B, max_context]``
cache wastes bandwidth proportional to (1 - occupancy). This kernel takes
per-row ``lengths [B]`` (the KV ledger) and

  * masks inside a block from ``lengths[b]`` (positions >= length get
    NEG_INF before the online softmax), and
  * skips KV blocks entirely past a row's length: the k/v index_map
    clamps the block index to the row's last in-range block, so the
    pipeline re-uses the already-resident block instead of issuing a new
    HBM stream, and ``pl.when`` skips the flash update. Streamed bytes
    scale with ceil(length/bc), not C/bc.

The cache is HEAD-MAJOR, ``[B, Kv, C, D]``: one grid step streams a
``(bc, D)`` block of one KV head, so the block's last two dims meet the
TPU tiling (``bc`` a multiple of the dtype's sublane tile, or all of C;
D whole). Grid = (B, Kv, C // bc); the flash state for the g query heads
of one KV group lives in VMEM scratch across the contraction steps.
Rows with length 0 (freshly-freed slots) execute no blocks and flush
zeros. ``return_block_counts=True`` also returns the executed-block
count per (row, KV head) — the structural quantity CI verifies, since
interpret mode has no meaningful wall clock; the serving path compiles
the kernel without that output.

``paged_decode_attention_pallas`` is the block-table mode
(``repro.runtime.paging``): K/V live in physical page pools
``[P, Kv, bs, D]`` and a second scalar-prefetched operand — the per-row
block table — is dereferenced by the K/V ``index_map`` to turn a logical
block step into a physical page fetch. A page block spans its whole
``(bs, D)`` tail, so any page size tiles. Same flash body, same skip
semantics; at ``bc == bs`` and identical logical contents the two modes
stream identical blocks in identical order, so outputs are bit-equal
(parity-locked in tests/test_paging.py).
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


def sublane_tile(dtype) -> int:
    """Rows of one TPU (sublane, lane) tile for ``dtype``: 8 for 32-bit,
    16 for 16-bit (two values packed per sublane), 32 for 8-bit."""
    return 8 * max(4 // jnp.dtype(dtype).itemsize, 1)


def largest_block_size(C: int, bc: int, multiple: int = 8) -> int:
    """The KV block size for a cache of ``C`` positions: all of C when it
    fits in ``bc``, else the largest divisor of C that is <= ``bc`` and a
    multiple of ``multiple`` (the sublane tile — a block the TPU can
    tile). Raises ``ValueError`` when C has no such divisor (a prime C
    larger than ``bc``): the caller picks another ``max_context``."""
    if C <= bc:
        return C
    for cand in range(bc - bc % multiple, 0, -multiple):
        if C % cand == 0:
            return cand
    raise ValueError(
        f"a cache of {C} positions has no block size <= {bc} that is a "
        f"multiple of {multiple}; the decode kernel cannot tile it")


def dense_kv_index_map(bc: int):
    """The dense-mode K/V ``index_map`` for block size ``bc`` over the
    head-major ``[B, Kv, C, D]`` cache: clamp past-length steps to the
    row's last in-range block, so the pipeline sees an unchanged block
    index and skips the HBM fetch. Module-level (not a closure inside the
    pallas_call wrapper) so ``repro.analysis.kernelcheck`` can evaluate
    the exact production index math over the full grid with concrete
    integers."""
    def kv_map(b, kv, c, lens):
        last = jnp.maximum((lens[b] + bc - 1) // bc, 1) - 1
        return (b, kv, jnp.minimum(c, last), 0)
    return kv_map


def paged_kv_index_map(bs: int):
    """The block-table K/V ``index_map`` for page size ``bs`` over the
    ``[P, Kv, bs, D]`` pools: clamp the logical block to the row's last
    in-range block, dereference the scalar-prefetched table, and clamp
    unallocated (-1) entries to the reserved scratch page 0. Module-level
    for ``repro.analysis.kernelcheck`` (see ``dense_kv_index_map``)."""
    def kv_map(b, kv, c, lens, tbl):
        last = jnp.maximum((lens[b] + bs - 1) // bs, 1) - 1
        page = tbl[b, jnp.minimum(c, last)]
        return (jnp.maximum(page, 0), kv, 0, 0)
    return kv_map


def _flash(len_ref, q_ref, load_kv, o_ref, rest, *, bc: int,
           n_c_steps: int, scale: float, with_counts: bool):
    """The flash loop every mode shares: ``load_kv()`` reads this grid
    step's (K [bc, Dk], V [bc, Dv]) block."""
    if with_counts:
        cnt_ref, m_ref, l_ref, acc_ref = rest
    else:
        cnt_ref = None
        m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    c_step = pl.program_id(2)
    length = len_ref[b]
    start = c_step * bc

    @pl.when(c_step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if cnt_ref is not None:
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

    @pl.when(start < length)
    def _compute():
        q = q_ref[0, 0]                              # [g, Dk]
        k, v = load_kv()                             # [bc, Dk], [bc, Dv]
        pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, bc), 1)
        valid = pos < length                         # [1, bc]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [g, bc]
        logits = jnp.where(valid, logits, NEG_INF)

        m_prev = m_ref[...]                          # [g, 1]
        m_new = jnp.maximum(m_prev, logits.max(-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = (acc_ref[...] * corr
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        if cnt_ref is not None:
            cnt_ref[...] += 1

    @pl.when(c_step == n_c_steps - 1)
    def _flush():
        # length-0 rows executed no block: acc == 0 flushes to exact zeros
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *rest, **kw):
    _flash(len_ref, q_ref, lambda: (k_ref[0, 0], v_ref[0, 0]), o_ref, rest,
           **kw)


def _paged_decode_kernel(len_ref, tbl_ref, q_ref, k_ref, v_ref, o_ref,
                         *rest, **kw):
    # the block table is consumed by the K/V index_maps (it decides WHICH
    # physical page each grid step streams); the flash body is untouched
    del tbl_ref
    _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *rest, **kw)


def _latent_decode_kernel(len_ref, q_ref, kv_ref, o_ref, *rest, dv: int,
                          **kw):
    # one streamed block serves as keys (all its columns) and values (the
    # first ``dv``): the latent cache is read once
    def load():
        kv = kv_ref[0, 0]
        return kv, kv[:, :dv]
    _flash(len_ref, q_ref, load, o_ref, rest, **kw)


def _decode_call(kernel, *, n_prefetch: int, B: int, Kv: int, g: int,
                 D: int, n_steps: int, blk: int, kv_map, out_dtype,
                 interpret: Optional[bool], with_counts: bool,
                 n_kv: int = 2, Dv: Optional[int] = None,
                 scale: Optional[float] = None):
    """The pallas_call every mode shares: the number of prefetched
    scalars, the K/V index_map, the block length, and the number of
    streamed caches (K and V, or one latent cache whose first ``Dv``
    columns are the values) differ."""
    Dv = D if Dv is None else Dv

    def head(b, kv, c, *_):
        return (b, kv, 0, 0)

    out_specs = [pl.BlockSpec((1, 1, g, Dv), head)]
    out_shape = [jax.ShapeDtypeStruct((B, Kv, g, Dv), out_dtype)]
    if with_counts:
        out_specs.append(pl.BlockSpec((1, 1, 1, 1), head))
        out_shape.append(jax.ShapeDtypeStruct((B, Kv, 1, 1), jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(B, Kv, n_steps),
        in_specs=[pl.BlockSpec((1, 1, g, D), head)]
        + [pl.BlockSpec((1, 1, blk, D), kv_map)] * n_kv,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, Dv), jnp.float32),
        ],
    )
    kernel = functools.partial(
        kernel, bc=blk, n_c_steps=n_steps,
        scale=1.0 / math.sqrt(D) if scale is None else scale,
        with_counts=with_counts)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret_mode(interpret),
        name="decode_attention")


def paged_decode_attention_pallas(q, k_pages, v_pages, lengths, block_table,
                                  *, interpret: Optional[bool] = None,
                                  return_block_counts: bool = False):
    """Block-table mode: q: [B,H,D]; k/v_pages: [P,Kv,bs,D] physical page
    pools; ``block_table`` int [B,max_blocks] maps row b's logical block
    l to a physical page (< 0 = unallocated); lengths int [B] -> [B,H,D].

    Same flash loop as the dense kernel, but the K/V ``index_map``
    dereferences the scalar-prefetched block table, so a row's KV stream
    follows its page chain instead of a contiguous [B, C] row. Past-length
    steps clamp to the row's last in-range LOGICAL block — the table entry
    (hence the physical page index) is unchanged, the pipeline re-uses the
    resident page, and ``pl.when`` skips the update; unallocated entries
    clamp to page 0 (never dereferenced by an in-length step of a
    correctly-tabled row, and row 0 of the pool is a reserved scratch
    page on the serving path). ``interpret`` defaults to "not on a TPU"."""
    B, H, D = q.shape
    Kv, bs = k_pages.shape[1], k_pages.shape[2]
    g = H // Kv
    n_blocks = block_table.shape[1]
    C = n_blocks * bs
    lens = jnp.clip(jnp.asarray(lengths, jnp.int32), 0, C)
    tbl = jnp.asarray(block_table, jnp.int32)
    outs = _decode_call(
        _paged_decode_kernel, n_prefetch=2, B=B, Kv=Kv, g=g, D=D,
        n_steps=n_blocks, blk=bs, kv_map=paged_kv_index_map(bs),
        out_dtype=q.dtype, interpret=interpret,
        with_counts=return_block_counts,
    )(lens, tbl, q.reshape(B, Kv, g, D), k_pages, v_pages)
    out = outs[0].reshape(B, H, D)
    if return_block_counts:
        return out, outs[1].reshape(B, Kv)
    return out


def decode_attention_pallas(q, k_cache, v_cache, lengths, *, bc: int = 512,
                            interpret: Optional[bool] = None,
                            return_block_counts: bool = False):
    """q: [B,H,D]; k/v_cache: [B,Kv,C,D]; lengths: int [B] -> [B,H,D].

    ``lengths[b]`` is the number of leading cache positions row b attends
    over (the KV ledger's context length); 0 yields a zero output row.
    ``bc`` is shrunk to a tile-aligned divisor of C when it does not tile
    (``largest_block_size``). ``interpret`` defaults to "not on a TPU".
    """
    B, H, D = q.shape
    Kv, C = k_cache.shape[1], k_cache.shape[2]
    g = H // Kv
    bc = largest_block_size(C, bc, sublane_tile(k_cache.dtype))
    lens = jnp.clip(jnp.asarray(lengths, jnp.int32), 0, C)
    outs = _decode_call(
        _decode_kernel, n_prefetch=1, B=B, Kv=Kv, g=g, D=D,
        n_steps=C // bc, blk=bc, kv_map=dense_kv_index_map(bc),
        out_dtype=q.dtype, interpret=interpret,
        with_counts=return_block_counts,
    )(lens, q.reshape(B, Kv, g, D), k_cache, v_cache)
    out = outs[0].reshape(B, H, D)
    if return_block_counts:
        return out, outs[1].reshape(B, Kv)
    return out


def latent_decode_attention_pallas(q, cache, lengths, *, dv: int,
                                   scale: float, bc: int = 512,
                                   interpret: Optional[bool] = None,
                                   return_block_counts: bool = False):
    """Latent mode (absorbed MLA decode): q: [B,H,Dk]; cache: [B,1,C,Dk],
    one latent stream per row shared by all H query heads; lengths: int
    [B] -> [B,H,dv].

    Keys are the cache's Dk columns, values its first ``dv`` columns of
    the same block, so each block is streamed once. ``scale`` multiplies
    the logits (MLA's is not 1/sqrt(Dk)). Same flash loop, dense index
    map and block skip as ``decode_attention_pallas``."""
    B, H, D = q.shape
    C = cache.shape[2]
    bc = largest_block_size(C, bc, sublane_tile(cache.dtype))
    lens = jnp.clip(jnp.asarray(lengths, jnp.int32), 0, C)
    outs = _decode_call(
        functools.partial(_latent_decode_kernel, dv=dv), n_prefetch=1, B=B,
        Kv=1, g=H, D=D, n_steps=C // bc, blk=bc,
        kv_map=dense_kv_index_map(bc), out_dtype=q.dtype,
        interpret=interpret, with_counts=return_block_counts, n_kv=1,
        Dv=dv, scale=scale,
    )(lens, q.reshape(B, 1, H, D), cache)
    out = outs[0].reshape(B, H, dv)
    if return_block_counts:
        return out, outs[1].reshape(B)
    return out
