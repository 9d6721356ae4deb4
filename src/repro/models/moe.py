"""Mixture-of-Experts layer: top-k router, routed experts (stacked weights,
expert-parallel friendly), optional shared experts (paper Fig. 1).

Two execution paths:
  * ``moe_apply_dense``    — exact all-experts einsum (oracle / tiny models)
  * ``moe_apply_capacity`` — GShard-style capacity dispatch with drops; the
    same dispatch/combine structure is what ``repro.core.dep`` shards with
    all_to_all (A2E/E2A) and chunks with FinDEP's r2.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.models.layers import dense_apply, dense_init, mlp_apply, mlp_init


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def moe_init(key, d_model: int, mcfg: MoEConfig, num_experts_padded: int = 0):
    """``num_experts_padded`` >= num_experts pads the expert dimension so it
    divides the expert-parallel mesh axis; padded experts are masked out in
    the router and receive no tokens."""
    E = num_experts_padded or mcfg.num_experts
    H = mcfg.expert_ffn_dim
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(d_model)
    params = {
        "router": dense_init(kr, d_model, E, scale=scale),
        "experts": {
            "gate": jax.random.normal(kg, (E, d_model, H), jnp.float32) * scale,
            "up": jax.random.normal(ku, (E, d_model, H), jnp.float32) * scale,
            "down": jax.random.normal(kd, (E, H, d_model), jnp.float32)
                    * (1.0 / math.sqrt(H)),
        },
    }
    if mcfg.num_shared_experts > 0:
        shared_H = (mcfg.shared_ffn_dim or H) * mcfg.num_shared_experts
        params["shared"] = mlp_init(ks, d_model, shared_H)
    return params


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

class Routing(NamedTuple):
    weights: jax.Array       # [T, k]  combine weights (top-k softmax
    #                                    probs, renormalized unless
    #                                    norm_topk_prob is False)
    experts: jax.Array       # [T, k]  int32 expert ids
    probs: jax.Array         # [T, E]  full softmax (for aux loss)


def route_topk(router_params, x_flat, mcfg: MoEConfig,
               num_experts_padded: int = 0) -> Routing:
    """x_flat: [T, M] -> top-k routing per token (paper §2.1)."""
    E_pad = num_experts_padded or mcfg.num_experts
    with jax.named_scope("moe/router"):
        logits = dense_apply(router_params, x_flat).astype(jnp.float32)
        if E_pad > mcfg.num_experts:              # mask padded experts
            neg = jnp.full((E_pad - mcfg.num_experts,), -1e30, jnp.float32)
            logits = logits.at[..., mcfg.num_experts:].set(neg)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, mcfg.top_k)
        if mcfg.norm_topk_prob:
            weights = weights / jnp.maximum(weights.sum(-1, keepdims=True),
                                            1e-9)
    return Routing(weights=weights, experts=experts.astype(jnp.int32),
                   probs=probs)


def load_balance_loss(routing: Routing, mcfg: MoEConfig) -> jax.Array:
    """Switch-style auxiliary loss: E * sum_e f_e * P_e over real experts."""
    E = mcfg.num_experts
    probs = routing.probs[..., :E]
    onehot = jax.nn.one_hot(routing.experts, probs.shape[-1])[..., :E]
    f = onehot.sum(axis=(-3, -2)) / (routing.experts.shape[0] * mcfg.top_k)
    p = probs.mean(axis=0)
    return E * jnp.sum(f * p)


# ---------------------------------------------------------------------------
# expert FFN (stacked einsum over the expert dimension)
#
# Each stage of the layer runs under a named scope (moe/router,
# moe/dispatch, moe/expert_ffn, moe/combine, moe/shared_expert): op
# metadata only, by which a device trace attributes time to the stage.
# ---------------------------------------------------------------------------

def expert_ffn(expert_params, x):
    """x: [E, C, M] -> [E, C, M] (one SwiGLU FFN per expert, Eq. 3)."""
    dt = x.dtype
    with jax.named_scope("moe/expert_ffn"):
        g = jnp.einsum("ecm,emh->ech", x, expert_params["gate"].astype(dt))
        u = jnp.einsum("ecm,emh->ech", x, expert_params["up"].astype(dt))
        return jnp.einsum("ech,ehm->ecm", jax.nn.silu(g) * u,
                          expert_params["down"].astype(dt))


def shared_expert_apply(params, x):
    """Dense shared-expert path (paper Eq. 2); fused over N_shared."""
    with jax.named_scope("moe/shared_expert"):
        return mlp_apply(params["shared"], x)


# ---------------------------------------------------------------------------
# execution path 1: exact dense combine (oracle)
# ---------------------------------------------------------------------------

def moe_apply_dense(params, x, mcfg: MoEConfig, num_experts_padded: int = 0,
                    return_stats: bool = False):
    """Computes every expert on every token and combines with routing
    weights. Exact (no capacity drops); O(E) compute. Returns (y, aux),
    or (y, aux, MoEStats) with ``return_stats``."""
    B, S, M = x.shape
    xf = x.reshape(-1, M)
    routing = route_topk(params["router"], xf, mcfg, num_experts_padded)
    E_pad = num_experts_padded or mcfg.num_experts
    with jax.named_scope("moe/dispatch"):
        # combine weights per (token, expert): [T, E]
        cw = jnp.zeros((xf.shape[0], E_pad), x.dtype)
        cw = cw.at[jnp.arange(xf.shape[0])[:, None],
                   routing.experts].add(routing.weights.astype(x.dtype))
        every = jnp.broadcast_to(xf, (E_pad,) + xf.shape)
    all_out = expert_ffn(params["experts"], every)
    with jax.named_scope("moe/combine"):
        y = jnp.einsum("te,etm->tm", cw, all_out)
    if "shared" in params:
        y = y + shared_expert_apply(params, xf)
    aux = load_balance_loss(routing, mcfg)
    y = y.reshape(B, S, M)
    if return_stats:
        load = jax.nn.one_hot(routing.experts, E_pad,
                              dtype=jnp.float32).sum(axis=(0, 1))
        stats = MoEStats(load=load, dropped=jnp.int32(0))
        return y, aux, stats
    return y, aux


# ---------------------------------------------------------------------------
# execution path 2: capacity-based dispatch (GShard) — shardable
# ---------------------------------------------------------------------------

class DispatchInfo(NamedTuple):
    buffers: jax.Array        # [E, C, M] dispatched tokens
    combine: jax.Array        # [T, k] combine weights (drops zeroed)
    slot: jax.Array           # [T, k] slot within expert buffer
    experts: jax.Array        # [T, k] PHYSICAL expert (buffer row) ids
    aux: jax.Array
    load: jax.Array           # [E] token-assignment counts, LOGICAL ids
    dropped: jax.Array        # []  capacity-overflow assignments (int32)


class MoEStats(NamedTuple):
    """Per-layer routing telemetry surfaced alongside (y, aux): the [E]
    token-load histogram (logical expert ids, float32 so meshes can
    psum-average it) and the count of capacity-overflow assignments that
    were dropped (previously silent — ISSUE 7 satellite bugfix)."""

    load: jax.Array           # [E] float32
    dropped: jax.Array        # []  int32


def expert_capacity(num_tokens: int, mcfg: MoEConfig,
                    num_experts_padded: int = 0, multiple_of: int = 1,
                    scale: float = 1.0) -> int:
    """``scale`` > 1 widens the per-expert buffer beyond the configured
    capacity factor — the skew-aware path sets it from the observed
    hottest-expert load so hot tokens are kept instead of dropped."""
    E = num_experts_padded or mcfg.num_experts
    cap = math.ceil(num_tokens * mcfg.top_k / E
                    * mcfg.capacity_factor * max(float(scale), 1.0))
    cap = max(cap, 1)
    return ((cap + multiple_of - 1) // multiple_of) * multiple_of


def expert_rows(num_tokens: int, mcfg: MoEConfig,
                num_experts_padded: int = 0, impl: str = "capacity") -> int:
    """Rows one MoE layer's routed-expert FFN computes for ``num_tokens``
    tokens on one device, padding included: every expert on every token
    (``impl="dense"``), else the capacity dispatch's [E, C] buffers."""
    E = num_experts_padded or mcfg.num_experts
    if impl == "dense":
        return E * num_tokens
    return E * expert_capacity(num_tokens, mcfg, num_experts_padded)


def moe_dispatch(params, xf, mcfg: MoEConfig, capacity: int,
                 num_experts_padded: int = 0,
                 expert_map: Optional[jax.Array] = None) -> DispatchInfo:
    """Route and scatter tokens into per-expert buffers [E, C, M].

    ``expert_map`` is an optional [E_pad] logical -> physical permutation
    (the active ``Placement.perm``): tokens routed to logical expert e
    land in buffer row ``expert_map[e]``, where that expert's weights
    live after a re-placement swap. ``load`` is always reported in
    LOGICAL ids (what the tracker and re-balancer reason about), and
    ``dropped`` counts the capacity-overflow assignments this dispatch
    silently zeroed before ISSUE 7."""
    T, M = xf.shape
    E_pad = num_experts_padded or mcfg.num_experts
    routing = route_topk(params["router"], xf, mcfg, num_experts_padded)
    with jax.named_scope("moe/dispatch"):
        experts = routing.experts
        load = jax.nn.one_hot(experts, E_pad,
                              dtype=jnp.float32).sum(axis=(0, 1))      # [E]
        if expert_map is not None:
            experts = expert_map.astype(jnp.int32)[experts]
        # position of each (token, k) within its expert, in token order
        onehot = jax.nn.one_hot(experts, E_pad, dtype=jnp.int32)      # [T,k,E]
        flat = onehot.reshape(T * mcfg.top_k, E_pad)
        pos = jnp.cumsum(flat, axis=0) - flat                          # [Tk,E]
        slot = (pos * flat).sum(-1).reshape(T, mcfg.top_k)             # [T,k]
        keep = slot < capacity
        weights = jnp.where(keep, routing.weights, 0.0)
        slot_c = jnp.where(keep, slot, capacity)  # drops -> scratch slot C
        dropped = (~keep).sum().astype(jnp.int32)
        buffers = jnp.zeros((E_pad, capacity + 1, M), xf.dtype)
        buffers = buffers.at[experts.reshape(-1),
                             slot_c.reshape(-1)].add(
            jnp.repeat(xf[:, None], mcfg.top_k, 1).reshape(-1, M))
        aux = load_balance_loss(routing, mcfg)
    return DispatchInfo(buffers=buffers[:, :capacity], combine=weights,
                        slot=slot_c, experts=experts, aux=aux,
                        load=load, dropped=dropped)


def moe_combine(info: DispatchInfo, expert_out: jax.Array, T: int,
                dtype) -> jax.Array:
    """Gather expert outputs back per token and apply combine weights."""
    M = expert_out.shape[-1]
    with jax.named_scope("moe/combine"):
        padded = jnp.concatenate(
            [expert_out, jnp.zeros((expert_out.shape[0], 1, M),
                                   expert_out.dtype)], axis=1)
        gathered = padded[info.experts.reshape(-1),
                          info.slot.reshape(-1)]             # [Tk, M]
        gathered = gathered.reshape(T, -1, M)
        return jnp.einsum("tk,tkm->tm", info.combine.astype(dtype),
                          gathered.astype(dtype))


def moe_apply_capacity(params, x, mcfg: MoEConfig,
                       num_experts_padded: int = 0,
                       capacity: Optional[int] = None,
                       return_stats: bool = False):
    """Single-device capacity-based MoE layer; the sharded/chunked variant
    lives in repro.core.dep. Returns (y, aux), or (y, aux, MoEStats)
    with ``return_stats``."""
    B, S, M = x.shape
    xf = x.reshape(-1, M)
    cap = capacity or expert_capacity(xf.shape[0], mcfg, num_experts_padded)
    info = moe_dispatch(params, xf, mcfg, cap, num_experts_padded)
    out = expert_ffn(params["experts"], info.buffers)
    y = moe_combine(info, out, xf.shape[0], x.dtype)
    if "shared" in params:
        y = y + shared_expert_apply(params, xf)
    y = y.reshape(B, S, M)
    if return_stats:
        return y, info.aux, MoEStats(load=info.load, dropped=info.dropped)
    return y, info.aux
