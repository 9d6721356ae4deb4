"""Attention variants: full causal, sliding-window/local, GQA decode with
full or ring-buffer KV caches, and MLA (DeepSeek-V2 latent attention).

All apply functions are pure; KV caches are explicit pytrees:
  full cache: {"k": [B,Kv,C,Dh], "v": [B,Kv,C,Dh], "index": i32[]}
  ring cache: same arrays with C == window; writes wrap at C.
  MLA cache:  {"latent": [B,1,C,W], "index": i32[]}: per position the
              normed compressed KV (r) and the roped key (dr) all heads
              share, laid out as one KV head, zero-padded to W, a whole
              number of 128-lane rows (``mla_cache_width``).
Caches are head-major so the decode kernel streams (positions, width)
blocks of one KV head, which tile on the TPU; ``SEQ_AXIS`` names each
cache entry's position axis.

MLA (arXiv:2405.04434, as DeepSeek-V2's modeling code computes it): per
head q = [q_nope ‖ rope(q_pe)]; [c ‖ k_pe] = x·W_kv_a, c_kv = RMSNorm(c),
k_pe roped once for all heads; [k_nope ‖ v] = c_kv·W_kv_b per head;
scores (q_nope·k_nope + q_pe·k_pe)·mscale²/sqrt(dn + dr). The prefill
expands K and V through W_kv_b for the prompt; the decode step absorbs
W_kv_b into the query (q_nope·W_uk) and the output (·W_uv) and attends
over the latent cache itself.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (apply_rope, dense_apply, dense_init,
                                 rmsnorm_apply, rmsnorm_init, yarn_mscale)

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

#: position axis of each cache entry
SEQ_AXIS = {"k": 2, "v": 2, "latent": 2}


def mla_dims(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    """(kv_lora_rank r, no-rope q/k width dn, rope width dr, value width
    dv) of an MLA config."""
    return (cfg.mla_kv_lora_rank, cfg.head_dim, cfg.mla_rope_dim,
            cfg.mla_v_dim)


def mla_cache_width(cfg: ModelConfig) -> int:
    """Width W of a latent cache row: r + dr rounded up to 128 lanes
    (576 -> 640). The TPU tiles a bf16 row-major cache as 128-lane rows
    either way; an unpadded 576-wide cache gets a position-minor device
    layout instead, which the decode kernel cannot read, and every decode
    step then relays out each layer's whole cache in and out."""
    r, _, dr, _ = mla_dims(cfg)
    return -(-(r + dr) // 128) * 128


def mla_scale(cfg: ModelConfig) -> float:
    """MLA's softmax scale: 1/sqrt(dn + dr), times YaRN's
    mscale(factor, mscale_all_dim)**2 where rope scaling states one."""
    _, dn, dr, _ = mla_dims(cfg)
    scale = 1.0 / math.sqrt(dn + dr)
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def attention_init(key, cfg: ModelConfig):
    hd = cfg.head_dim
    if cfg.mla_kv_lora_rank:
        kq, ka, kb, ko = jax.random.split(key, 4)
        r, dn, dr, dv = mla_dims(cfg)
        H = cfg.num_heads
        return {
            "wq": dense_init(kq, cfg.d_model, H * (dn + dr)),
            "w_kv_a": dense_init(ka, cfg.d_model, r + dr),
            "kv_norm": rmsnorm_init(r),
            "w_kv_b": dense_init(kb, r, H * (dn + dv)),
            "wo": dense_init(ko, H * dv, cfg.d_model),
        }
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, cfg.d_model, cfg.num_heads * hd, bias=cfg.qkv_bias),
        "wk": dense_init(kk, cfg.d_model, cfg.num_kv_heads * hd, bias=cfg.qkv_bias),
        "wv": dense_init(kv, cfg.d_model, cfg.num_kv_heads * hd, bias=cfg.qkv_bias),
        "wo": dense_init(ko, cfg.num_heads * hd, cfg.d_model),
    }


# ---------------------------------------------------------------------------
# cache management
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, capacity: int,
                  dtype=jnp.bfloat16):
    hd = cfg.head_dim
    if cfg.mla_kv_lora_rank:
        return {
            "latent": jnp.zeros((batch, 1, capacity, mla_cache_width(cfg)),
                                dtype),
            "index": jnp.zeros((), jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, cfg.num_kv_heads, capacity, hd), dtype),
        "v": jnp.zeros((batch, cfg.num_kv_heads, capacity, hd), dtype),
        "index": jnp.zeros((), jnp.int32),
    }


def cache_capacity(cfg: ModelConfig, seq_len: int) -> int:
    """Ring caches (sliding/local attention) cap capacity at the window."""
    if cfg.attention in ("sliding", "local"):
        return min(seq_len, cfg.sliding_window)
    return seq_len


# ---------------------------------------------------------------------------
# masking helpers
# ---------------------------------------------------------------------------

def _causal_mask(q_pos, k_pos, window: Optional[int]):
    """q_pos: [S_q], k_pos: [S_k] (absolute). True == attend."""
    m = q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    return m


def _flash_sdpa_xla(q, k, v, q_pos, k_pos, window: Optional[int],
                    q_chunk: int = 512, k_chunk: int = 1024,
                    scale: Optional[float] = None):
    """Flash-attention structured as pure XLA: outer scan over query chunks,
    inner scan over key chunks with online softmax. Never materializes more
    than [B, Kv, g, q_chunk, k_chunk] of logits. Used for long sequences
    where the [S, S] score matrix of `_sdpa` would not fit.

    q/k: [B,S,H|Kv,D]; v: [B,S,Kv,Dv]; q_pos/k_pos: [S] absolute
    positions; ``scale`` defaults to 1/sqrt(D).
    """
    B, S, H, D = q.shape
    Kv, Dv = k.shape[2], v.shape[3]
    g = H // Kv
    q_chunk = min(q_chunk, S)
    k_chunk = min(k_chunk, S)
    nq = (S + q_chunk - 1) // q_chunk
    nk = (S + k_chunk - 1) // k_chunk
    Sp_q, Sp_k = nq * q_chunk, nk * k_chunk
    scale = 1.0 / math.sqrt(D) if scale is None else scale

    def pad_seq(x, Sp):
        return jnp.pad(x, ((0, 0), (0, Sp - x.shape[1])) + ((0, 0),) *
                       (x.ndim - 2))

    qp = pad_seq(q, Sp_q).reshape(B, nq, q_chunk, Kv, g, D)
    kp = pad_seq(k, Sp_k).reshape(B, nk, k_chunk, Kv, D)
    vp = pad_seq(v, Sp_k).reshape(B, nk, k_chunk, Kv, Dv)
    qpos = jnp.pad(q_pos, (0, Sp_q - S), constant_values=-1)
    qpos = qpos.reshape(nq, q_chunk)
    kpos = jnp.pad(k_pos, (0, Sp_k - S), constant_values=2**30)
    kpos = kpos.reshape(nk, k_chunk)

    def outer(_, qc):
        q_blk, qp_blk = qc                       # [B,c,Kv,g,D], [c]

        def inner(carry, kc):
            m, l, acc = carry
            k_blk, v_blk, kp_blk = kc
            logits = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k_blk,
                                preferred_element_type=jnp.float32) * scale
            msk = qp_blk[:, None] >= kp_blk[None, :]
            if window is not None:
                msk &= (qp_blk[:, None] - kp_blk[None, :]) < window
            logits = jnp.where(msk[None, None, None], logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v_blk.dtype),
                            v_blk).astype(jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Kv, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Kv, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Kv, g, q_chunk, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            inner, (m0, l0, a0),
            (kp.swapaxes(0, 1), vp.swapaxes(0, 1), kpos))
        out = acc / jnp.maximum(l, 1e-30)[..., None].astype(acc.dtype)
        return None, out.astype(q.dtype)         # [B,Kv,g,c,D]

    _, outs = jax.lax.scan(outer, None,
                           (qp.swapaxes(0, 1), qpos))      # [nq,B,Kv,g,c,D]
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sp_q, H, Dv)
    return out[:, :S]


def _sdpa(q, k, v, mask, scale: Optional[float] = None):
    """q: [B,Sq,H,Dh], k: [B,Sk,Kv,Dh], v: [B,Sk,Kv,Dv] (GQA broadcast),
    mask [Sq,Sk] or [B,Sq,Sk]; ``scale`` defaults to 1/sqrt(Dh)."""
    B, Sq, H, Dh = q.shape
    Kv = k.shape[2]
    groups = H // Kv
    q = q.reshape(B, Sq, Kv, groups, Dh)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                        preferred_element_type=jnp.float32)
    logits = (logits / math.sqrt(Dh) if scale is None
              else logits * scale)
    if mask.ndim == 2:
        mask = mask[None, None, None]
    else:
        mask = mask[:, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, H, v.shape[-1])


# ---------------------------------------------------------------------------
# full-sequence (train / prefill) attention
# ---------------------------------------------------------------------------

def _mla_project(params, cfg: ModelConfig, x, positions):
    """x: [B,S,M] -> (q_nope [B,S,H,dn], roped q_pe [B,S,H,dr], latent
    [B,S,W]: RMSNorm(c_kv) ‖ the roped shared key ‖ zeros, a cache row)."""
    B, S, _ = x.shape
    r, dn, dr, _ = mla_dims(cfg)
    q = dense_apply(params["wq"], x).reshape(B, S, cfg.num_heads, dn + dr)
    q_pe = apply_rope(q[..., dn:], positions, cfg.rope_theta,
                      cfg.rope_scaling)
    kv_a = dense_apply(params["w_kv_a"], x)                     # [B,S,r+dr]
    c_kv = rmsnorm_apply(params["kv_norm"], kv_a[..., :r], cfg.norm_eps)
    k_pe = apply_rope(kv_a[..., None, r:], positions, cfg.rope_theta,
                      cfg.rope_scaling)[:, :, 0]
    pad = jnp.zeros((B, S, mla_cache_width(cfg) - r - dr), c_kv.dtype)
    return q[..., :dn], q_pe, jnp.concatenate([c_kv, k_pe, pad], -1)


def _mla_expand(params, cfg: ModelConfig, q_nope, q_pe, latent):
    """The prefill's K and V for the prompt's own positions: [k_nope ‖
    k_pe] [B,S,H,dn+dr] and v [B,S,H,dv] from c_kv·W_kv_b, with the query
    [q_nope ‖ q_pe]."""
    B, S, H, _ = q_nope.shape
    r, dn, dr, dv = mla_dims(cfg)
    with jax.named_scope("kv_up"):
        kv = dense_apply(params["w_kv_b"], latent[..., :r])
        kv = kv.reshape(B, S, H, dn + dv)
    k_pe = jnp.broadcast_to(latent[:, :, None, r:r + dr], (B, S, H, dr))
    k = jnp.concatenate([kv[..., :dn], k_pe], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    return q, k, kv[..., dn:]


def _project_qkv(params, cfg: ModelConfig, x, positions):
    B, S, _ = x.shape
    hd = cfg.head_dim
    if cfg.mla_kv_lora_rank:
        q_nope, q_pe, latent = _mla_project(params, cfg, x, positions)
        q, k, v = _mla_expand(params, cfg, q_nope, q_pe, latent)
        return q, k, v, {"latent": latent[:, None]}
    q = dense_apply(params["wq"], x).reshape(B, S, cfg.num_heads, hd)
    k = dense_apply(params["wk"], x).reshape(B, S, cfg.num_kv_heads, hd)
    v = dense_apply(params["wv"], x).reshape(B, S, cfg.num_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v, {"k": k.swapaxes(1, 2), "v": v.swapaxes(1, 2)}


def attention_fullseq(params, cfg: ModelConfig, x, positions,
                      cache: Optional[dict] = None, impl: str = "xla"):
    """Training / prefill attention over the whole sequence.

    If ``cache`` is given it is filled with this segment's K/V (prefill);
    returns (out, cache_or_None).
    """
    B, S, _ = x.shape
    window = cfg.sliding_window if cfg.attention in ("sliding", "local") else None
    q, k, v, to_cache = _project_qkv(params, cfg, x, positions)
    scale = mla_scale(cfg) if cfg.mla_kv_lora_rank else None

    if impl == "flash" and cfg.mla_kv_lora_rank == 0:
        from repro.kernels.flash_attention import ops as flash_ops
        out = flash_ops.flash_attention(q, k, v, causal=True, window=window)
    elif impl == "chunked" or (impl in ("xla", "decode_kernel")
                               and S > 2048):
        out = _flash_sdpa_xla(q, k, v, positions[0], positions[0], window,
                              scale=scale)
    else:
        mask = _causal_mask(positions[0], positions[0], window)
        out = _sdpa(q, k, v, mask, scale)

    out = dense_apply(params["wo"], out.reshape(B, S, -1))
    new_cache = None
    if cache is not None:
        new_cache = _prefill_cache(cfg, cache, to_cache, S)
    return out, new_cache


def _cache_len(cfg: ModelConfig, cache) -> int:
    name = "latent" if cfg.mla_kv_lora_rank else "k"
    return cache[name].shape[SEQ_AXIS[name]]


def _prefill_cache(cfg: ModelConfig, cache, to_cache, S: int):
    C = _cache_len(cfg, cache)
    new = dict(cache)
    keep = min(S, C)
    for name, val in to_cache.items():
        ax = SEQ_AXIS[name]
        seg = jax.lax.slice_in_dim(val, S - keep, S, axis=ax)
        if keep == C and S % C:
            # ring-cache invariant: slot s holds absolute position == s (mod
            # C). Token at absolute pos p lands in slot p % C; the kept
            # segment covers positions [S-C, S), so roll by (S-C) % C == S%C.
            seg = jnp.roll(seg, S % C, axis=ax)
        new[name] = jax.lax.dynamic_update_slice_in_dim(
            cache[name], seg.astype(cache[name].dtype), 0, axis=ax)
    new["index"] = jnp.asarray(S, jnp.int32)
    return new


# ---------------------------------------------------------------------------
# distributed decode over a sequence-sharded KV cache
# ---------------------------------------------------------------------------

def use_seqsharded_decode(cfg: ModelConfig, mesh, axis: str,
                          capacity: int) -> bool:
    """Sequence-shard the decode cache over the model axis when the KV-head
    count does not divide it (GQA with few KV heads). The attention core
    then runs as a distributed flash combine (local partial softmax stats +
    psum), implemented in shard_map — GSPMD's fallback for this pattern is
    a per-layer all-gather of the whole cache."""
    if mesh is None or axis not in mesh.axis_names:
        return False
    mo = mesh.shape[axis]
    if cfg.mla_kv_lora_rank:
        return False
    return (cfg.num_kv_heads % mo != 0) and capacity % mo == 0


def _decode_core_seqsharded(q, k_new, v_new, cache_k, cache_v, index,
                            mesh, axis: str, batch_axes, is_ring: bool):
    """q: [B,1,H,Dh]; k_new/v_new: [B,Kv,1,Dh]; cache_[kv]: [B,Kv,C,Dh]
    sequence-sharded over ``axis``. Returns (out [B,1,H,Dh], new caches).
    """
    from jax.sharding import PartitionSpec as P

    mo = mesh.shape[axis]
    B, Kv = cache_k.shape[0], cache_k.shape[1]
    C, Dh = cache_k.shape[2], cache_k.shape[3]
    H = q.shape[2]
    g = H // Kv
    C_loc = C // mo
    b = batch_axes
    scale = 1.0 / math.sqrt(Dh)

    def local(q, k_new, v_new, ck, cv, index):
        i = jax.lax.axis_index(axis)
        slot = index % C if is_ring else jnp.minimum(index, C - 1)
        loc = slot - i * C_loc
        in_range = (loc >= 0) & (loc < C_loc)
        loc_c = jnp.clip(loc, 0, C_loc - 1)
        upd_k = jax.lax.dynamic_update_slice_in_dim(
            ck, k_new.astype(ck.dtype), loc_c, 2)
        upd_v = jax.lax.dynamic_update_slice_in_dim(
            cv, v_new.astype(cv.dtype), loc_c, 2)
        ck = jnp.where(in_range, upd_k, ck)
        cv = jnp.where(in_range, upd_v, cv)

        slots = i * C_loc + jnp.arange(C_loc, dtype=jnp.int32)
        if is_ring:
            base = ((index - slots) // C) * C + slots
            k_pos = jnp.where(base > index, base - C, base)
            valid = (k_pos >= 0) & (k_pos <= index) & (index - k_pos < C)
        else:
            valid = slots <= index

        qh = q.reshape(q.shape[0], Kv, g, Dh)
        logits = jnp.einsum("bkgd,bksd->bkgs", qh, ck.astype(qh.dtype),
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(valid[None, None, None], logits, NEG_INF)
        m_loc = logits.max(-1)
        m = jax.lax.pmax(m_loc, axis)
        p = jnp.exp(logits - m[..., None])
        p = jnp.where(valid[None, None, None], p, 0.0)
        l = jax.lax.psum(p.sum(-1), axis)
        o = jnp.einsum("bkgs,bksd->bkgd", p.astype(cv.dtype), cv)
        o = jax.lax.psum(o.astype(jnp.float32), axis)
        out = (o / jnp.maximum(l, 1e-30)[..., None])
        return out.reshape(q.shape[0], 1, H, Dh).astype(q.dtype), ck, cv

    cache_spec = P(b, None, axis, None)
    out, ck, cv = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(b, None, None), P(b, None, None, None),
                  P(b, None, None, None), cache_spec, cache_spec, P()),
        out_specs=(P(b, None, None, None), cache_spec, cache_spec),
        check_vma=False,
    )(q[:, 0], k_new, v_new, cache_k, cache_v, index)
    return out, ck, cv


# ---------------------------------------------------------------------------
# single-token decode
# ---------------------------------------------------------------------------

def _per_device(kernel, mesh, axis: str, q, k, v, lens, *table,
                paged: bool = False):
    """Run a decode kernel on a mesh. GSPMD cannot partition a Mosaic
    kernel, so on more than one device each device runs it on its own KV
    heads (split over ``axis``; the GQA groups stay whole, since H and Kv
    split alike) and its own batch rows (over the data axes that divide
    B). Page pools hold every row's pages, so they split by head only."""
    if mesh is None or mesh.size == 1:
        return kernel(q, k, v, lens, *table)
    from jax.sharding import PartitionSpec as P
    from repro.sharding.partition import batch_pspec
    b = batch_pspec(q.shape[0], mesh, exclude=(axis,))[0]
    kv_spec = (P(None, axis, None, None) if paged
               else P(b, axis, None, None))
    in_specs = (P(b, axis, None), kv_spec, kv_spec, P(b))
    if table:
        in_specs += (P(b, None),)
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                         out_specs=P(b, axis, None),
                         check_vma=False)(q, k, v, lens, *table)


def attention_decode(params, cfg: ModelConfig, x, cache, impl: str = "xla",
                     ctx=None, lengths=None, block_table=None):
    """x: [B, 1, M]; cache index == number of tokens already cached.
    ``lengths`` ([B] int, optional) is the KV ledger's per-slot context
    length — the positions THIS step attends over. When given (the
    continuous-batching engine passes it once per step), the attention
    mask comes from the ledger instead of being recomputed per layer
    from the cache index, and the ragged Pallas decode kernel can skip
    KV blocks past each row's length. ``block_table`` (int
    [B, max_blocks], optional) switches the cache to the PAGED layout
    (``repro.runtime.paging``): k/v are physical page pools and each
    row's KV stream follows its page chain. Returns (out [B,1,M],
    updated cache)."""
    if block_table is not None:
        return _attention_decode_paged(params, cfg, x, cache, impl, ctx,
                                       lengths, block_table)
    B = x.shape[0]
    index = jnp.asarray(cache["index"])
    positions = (jnp.full((B, 1), index, jnp.int32) if index.ndim == 0
                 else index[:, None].astype(jnp.int32))
    if cfg.mla_kv_lora_rank:
        q_nope, q_pe, latent = _mla_project(params, cfg, x, positions)
        to_cache = {"latent": latent[:, None]}
    else:
        q, k, v, to_cache = _project_qkv(params, cfg, x, positions)

    C = _cache_len(cfg, cache)
    is_ring = cfg.attention in ("sliding", "local")
    if impl == "decode_kernel" and is_ring:
        raise ValueError(
            "the decode kernel takes full-attention caches "
            f"(attention={cfg.attention!r}); pass attn_impl='xla' for "
            "this model")

    mesh = getattr(ctx, "mesh", None)
    axis = getattr(ctx, "expert_axis", "model")
    if index.ndim == 0 and use_seqsharded_decode(cfg, mesh, axis, C):
        if impl == "decode_kernel":
            raise ValueError(
                f"{cfg.num_kv_heads} KV heads do not divide the "
                f"{mesh.shape[axis]}-way {axis!r} axis: the cache is "
                "sequence-sharded and the decode kernel cannot take it; "
                "pass attn_impl='xla'")
        from repro.sharding.partition import batch_pspec
        bspec = batch_pspec(cache["k"].shape[0], mesh)
        b_axes = bspec[0] if bspec != jax.sharding.PartitionSpec(None) else None
        out, ck, cv = _decode_core_seqsharded(
            q, to_cache["k"], to_cache["v"], cache["k"], cache["v"], index,
            mesh, axis, b_axes, is_ring)
        new_cache = dict(cache, k=ck, v=cv, index=index + 1)
        out = dense_apply(params["wo"], out.reshape(B, 1, -1))
        return out, new_cache

    slot = jnp.where(jnp.asarray(is_ring), index % C, jnp.minimum(index, C - 1))

    new_cache = dict(cache)
    if index.ndim == 0:
        for name, val in to_cache.items():
            new_cache[name] = jax.lax.dynamic_update_slice_in_dim(
                cache[name], val.astype(cache[name].dtype), slot,
                axis=SEQ_AXIS[name])
    else:
        # per-sample indices (continuous batching): one row each
        with jax.named_scope("kv_write"):
            for name, val in to_cache.items():
                new_cache[name] = _write_rows(cache[name], val, slot,
                                              SEQ_AXIS[name])
    new_cache["index"] = index + 1

    # per-row attended prefix (non-ring): the ledger's context length when
    # plumbed in, else recovered from the cache index (index counts the
    # tokens cached BEFORE this step's write, so the attended prefix —
    # including the row just written — is index + 1)
    slots = jnp.arange(C, dtype=jnp.int32)
    if is_ring:
        # slot s holds absolute pos: the latest write to s at or before index
        idx = index if index.ndim == 0 else index[:, None]     # [] or [B,1]
        base = ((idx - slots) // C) * C + slots
        k_pos = jnp.where(base > idx, base - C, base)
        valid = (k_pos >= 0) & (k_pos <= idx) & (idx - k_pos < C)
        mask = valid[None, :] if index.ndim == 0 else valid[:, None, :]
        lens = None
    else:
        if lengths is not None:
            lens = jnp.clip(jnp.asarray(lengths, jnp.int32), 0, C)   # [B]
        elif index.ndim == 0:
            lens = jnp.full((B,), jnp.minimum(index + 1, C), jnp.int32)
        else:
            lens = jnp.minimum(index.astype(jnp.int32) + 1, C)
        mask = slots[None, None, :] < lens[:, None, None]      # [B, 1, C]

    if cfg.mla_kv_lora_rank:
        out = _mla_decode_core(params, cfg, q_nope[:, 0], q_pe[:, 0],
                               new_cache["latent"].astype(x.dtype), lens,
                               impl, getattr(ctx, "decode_bc", None))
    elif impl == "decode_kernel":
        # the serving path: ragged Pallas kernel streams ceil(len/bc)
        # blocks per row instead of the dense [B, C] cache
        from repro.kernels.decode_attention import ops as dec_ops
        bc = getattr(ctx, "decode_bc", None) or 512
        out = _per_device(
            functools.partial(dec_ops.decode_attention, bc=bc), mesh, axis,
            q[:, 0], new_cache["k"].astype(x.dtype),
            new_cache["v"].astype(x.dtype), lens)
        out = out[:, None]
    else:
        k_all = new_cache["k"].astype(x.dtype).swapaxes(1, 2)
        v_all = new_cache["v"].astype(x.dtype).swapaxes(1, 2)
        out = _sdpa(q, k_all, v_all, mask)
    out = dense_apply(params["wo"], out.reshape(B, 1, -1))
    return out, new_cache


def _write_rows(arr, val, pos, axis: int):
    """Row b of ``val`` (one position along ``axis``) written at position
    ``pos[b]`` of row b of ``arr``, by one ``dynamic_update_slice`` a row.
    These keep the cache in its own layout, so a donated cache is updated
    in place; a scatter over (row, position) pairs runs in a heads-minor
    layout, and XLA copies the whole cache into it and back out."""
    val = val.astype(arr.dtype)
    for b in range(arr.shape[0]):
        start = [0] * arr.ndim
        start[0], start[axis] = b, pos[b]
        arr = jax.lax.dynamic_update_slice(arr, val[b:b + 1], start)
    return arr


def _mla_decode_core(params, cfg: ModelConfig, q_nope, q_pe, latent, lens,
                     impl: str, bc: Optional[int]):
    """Absorbed MLA decode: q_nope [B,H,dn], q_pe [B,H,dr], latent cache
    [B,1,C,W], lens [B] -> [B,H,dv]. The query takes W_uk (per head,
    dn -> r), attention runs over the latent cache (keys all W columns,
    the query zero past r + dr; values the first r), and the output takes
    W_uv (r -> dv): W_kv_b never multiplies the cache."""
    from repro.kernels.decode_attention import ops as dec_ops
    r, dn, dr, dv = mla_dims(cfg)
    w_kv_b = params["w_kv_b"]["kernel"].astype(q_nope.dtype)
    w_kv_b = w_kv_b.reshape(r, cfg.num_heads, dn + dv)
    with jax.named_scope("q_absorb"):
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, w_kv_b[..., :dn])
    pad = jnp.zeros(q_pe.shape[:-1] + (latent.shape[-1] - r - dr,),
                    q_pe.dtype)
    q = jnp.concatenate([q_lat, q_pe, pad], -1)                # [B,H,W]
    o_lat = dec_ops.decode_attention_latent(
        q, latent, lens, dv=r, scale=mla_scale(cfg), bc=bc or 512,
        use_kernel=impl == "decode_kernel")
    with jax.named_scope("v_up"):
        return jnp.einsum("bhr,rhd->bhd", o_lat, w_kv_b[..., dn:])


def _attention_decode_paged(params, cfg: ModelConfig, x, cache, impl, ctx,
                            lengths, block_table):
    """Paged-KV decode: the cache's k/v are physical page pools
    ``[P, Kv, bs, D]`` shared by every slot, and ``block_table`` (int
    [B, max_blocks]) maps each row's logical blocks to pages (< 0 =
    unallocated). The new token scatters into its row's tail page at
    ``index % bs``; dead rows (no pages) clamp to the reserved scratch
    page 0, so they never corrupt live KV. The engine gates
    ``kv_layout='paged'`` to full-attention GQA — no MLA, no ring, no
    seq-sharded decode."""
    if cfg.mla_kv_lora_rank or cfg.attention in ("sliding", "local"):
        raise NotImplementedError(
            "paged KV decode requires full-attention GQA "
            f"(attention={cfg.attention!r}, mla={cfg.mla_kv_lora_rank})")
    B = x.shape[0]
    bs = cache["k"].shape[2]
    C = block_table.shape[1] * bs
    index = jnp.asarray(cache["index"])
    if index.ndim == 0:
        index = jnp.full((B,), index, jnp.int32)
    positions = index[:, None].astype(jnp.int32)
    q, _, _, to_cache = _project_qkv(params, cfg, x, positions)

    tbl = jnp.asarray(block_table, jnp.int32)
    pos = jnp.minimum(index.astype(jnp.int32), C - 1)
    phys = jnp.maximum(tbl[jnp.arange(B), pos // bs], 0)
    new_cache = dict(cache)
    for name, val in to_cache.items():
        new_cache[name] = cache[name].at[phys, :, pos % bs].set(
            val[:, :, 0].astype(cache[name].dtype))
    new_cache["index"] = index + 1

    if lengths is not None:
        lens = jnp.clip(jnp.asarray(lengths, jnp.int32), 0, C)
    else:
        lens = jnp.minimum(index.astype(jnp.int32) + 1, C)

    if impl == "decode_kernel":
        from repro.kernels.decode_attention import ops as dec_ops
        out = _per_device(dec_ops.decode_attention_paged,
                          getattr(ctx, "mesh", None),
                          getattr(ctx, "expert_axis", "model"),
                          q[:, 0], new_cache["k"], new_cache["v"], lens,
                          tbl, paged=True)
        out = out[:, None]
    else:
        from repro.kernels.decode_attention.ref import gather_pages
        k_all = gather_pages(new_cache["k"], tbl).astype(x.dtype)
        v_all = gather_pages(new_cache["v"], tbl).astype(x.dtype)
        k_all, v_all = k_all.swapaxes(1, 2), v_all.swapaxes(1, 2)
        mask = (jnp.arange(C, dtype=jnp.int32)[None, None, :]
                < lens[:, None, None])                         # [B, 1, C]
        out = _sdpa(q, k_all, v_all, mask)
    out = dense_apply(params["wo"], out.reshape(B, 1, -1))
    return out, new_cache


# ---------------------------------------------------------------------------
# cross attention (encoder-decoder)
# ---------------------------------------------------------------------------

def cross_attention_init(key, cfg: ModelConfig):
    kq, kk, kv, ko = jax.random.split(key, 4)
    hd = cfg.head_dim
    return {
        "wq": dense_init(kq, cfg.d_model, cfg.num_heads * hd),
        "wk": dense_init(kk, cfg.d_model, cfg.num_kv_heads * hd),
        "wv": dense_init(kv, cfg.d_model, cfg.num_kv_heads * hd),
        "wo": dense_init(ko, cfg.num_heads * hd, cfg.d_model),
    }


def cross_attention_apply(params, cfg: ModelConfig, x, memory):
    """x: [B,Sq,M] decoder states; memory: [B,Sk,M] encoder output."""
    B, Sq, _ = x.shape
    Sk = memory.shape[1]
    hd = cfg.head_dim
    q = dense_apply(params["wq"], x).reshape(B, Sq, cfg.num_heads, hd)
    k = dense_apply(params["wk"], memory).reshape(B, Sk, cfg.num_kv_heads, hd)
    v = dense_apply(params["wv"], memory).reshape(B, Sk, cfg.num_kv_heads, hd)
    mask = jnp.ones((Sq, Sk), bool)
    out = _sdpa(q, k, v, mask)
    return dense_apply(params["wo"], out.reshape(B, Sq, -1))
