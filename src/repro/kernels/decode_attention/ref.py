"""Pure-jnp oracle for single-token GQA decode attention over a ragged,
head-major KV cache: row b attends the first ``lengths[b]`` cache
positions."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def decode_attention_ref(q, k_cache, v_cache, lengths):
    """q: [B,H,D]; k/v_cache: [B,Kv,C,D]; lengths: int [B] -> [B,H,D].

    Length-0 rows (freshly-freed slots) return exact zeros — a dense
    softmax over an all-masked row would return the mean of V instead.
    """
    B, H, D = q.shape
    Kv, C = k_cache.shape[1], k_cache.shape[2]
    g = H // Kv
    lengths = jnp.asarray(lengths, jnp.int32)
    qh = q.reshape(B, Kv, g, D).astype(jnp.float32)
    valid = jnp.arange(C)[None, :] < lengths[:, None]          # [B, C]
    logits = jnp.einsum("bkgd,bksd->bkgs", qh,
                        k_cache.astype(jnp.float32)) / math.sqrt(D)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", p, v_cache.astype(jnp.float32))
    out = jnp.where((lengths > 0)[:, None, None, None], out, 0.0)
    return out.reshape(B, H, D).astype(q.dtype)


def latent_decode_attention_ref(q, cache, lengths, *, dv: int,
                               scale: float):
    """Oracle for the latent mode: q: [B,H,Dk]; cache: [B,1,C,Dk] (keys
    all Dk columns, values the first ``dv``); lengths: int [B] ->
    [B,H,dv]. Length-0 rows return exact zeros."""
    lengths = jnp.asarray(lengths, jnp.int32)
    lat = cache[:, 0].astype(jnp.float32)                      # [B,C,Dk]
    valid = jnp.arange(lat.shape[1])[None, :] < lengths[:, None]
    logits = jnp.einsum("bhd,bcd->bhc", q.astype(jnp.float32), lat) * scale
    logits = jnp.where(valid[:, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhc,bcd->bhd", p, lat[..., :dv])
    out = jnp.where((lengths > 0)[:, None, None], out, 0.0)
    return out.astype(q.dtype)


def gather_pages(pages, block_table):
    """[P,Kv,bs,D] pages + int [B,n] table -> dense [B, Kv, n*bs, D].
    Unallocated entries (< 0) clamp to page 0; their positions sit past
    the row's length, so the masked attention never reads them."""
    B, n = block_table.shape
    Kv, bs, D = pages.shape[1], pages.shape[2], pages.shape[3]
    tbl = jnp.maximum(jnp.asarray(block_table, jnp.int32), 0)
    return pages[tbl].transpose(0, 2, 1, 3, 4).reshape(B, Kv, n * bs, D)


def paged_decode_attention_ref(q, k_pages, v_pages, lengths, block_table):
    """Oracle for the paged kernel: gather each row's page chain into a
    dense cache, then run the dense reference."""
    return decode_attention_ref(q, gather_pages(k_pages, block_table),
                                gather_pages(v_pages, block_table), lengths)
