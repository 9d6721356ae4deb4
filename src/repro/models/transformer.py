"""Model assembly for all architecture families.

One ``Model`` class covers:
  dense / moe / vlm   — causal transformer (GQA or MLA), MLP or MoE FFN
  ssm                 — xLSTM stacks (mLSTM / sLSTM pattern)
  hybrid              — RecurrentGemma (RG-LRU + local attention, 1:2)
  audio               — encoder-decoder (encoder consumes stub frame embeds)

Execution modes:
  forward()      full-sequence (training forward / loss)
  prefill()      full-sequence + cache fill
  decode_step()  one token with cache
Layers run as a Python loop (``scan_layers=False``, default: simplest,
exact) or as ``lax.scan`` over stacked per-pattern-group parameters
(``scan_layers=True``: small HLO for the 126-layer dry-runs).

MoE layers dispatch through ``moe_impl``:
  "dense"     exact all-experts oracle
  "capacity"  GShard capacity dispatch (single device)
  "dep"       FinDEP-scheduled expert-parallel path (repro.core.dep);
              requires an ExecutionContext with a mesh; the schedule
              ``Plan`` is passed per call (forward/prefill/decode_step all
              take ``plan=``) so one compiled model serves every schedule
              a repro.sched.SchedulePolicy resolves.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (dense_apply, dense_init, embedding_apply,
                                 embedding_attend, embedding_init, mlp_apply,
                                 mlp_init, rmsnorm_apply, rmsnorm_init)


@dataclass(frozen=True)
class ExecutionContext:
    """Immutable distribution template threaded to layers that need
    collectives. Schedules are NOT part of the context: the per-shape
    ``Plan``/``ExecProgram`` flows through the model call
    (``forward(..., plan=...)``), resolved by a
    ``repro.sched.SchedulePolicy``."""

    mesh: Optional[Any] = None          # jax Mesh (None = single device)
    expert_axis: str = "model"          # mesh axis used for EP / A2E-E2A
    data_axes: Tuple[str, ...] = ("data",)
    attn_impl: str = "xla"              # "xla" | "flash" | "decode_kernel"
    moe_impl: str = "capacity"          # "dense" | "capacity" | "dep"
    remat: bool = False
    #: decode-kernel KV block size override (None = kernel default). The
    #: paged engine pins its DENSE comparison runs to the page size so
    #: paged-vs-dense parity is bitwise (same block order, same flash
    #: accumulation grouping).
    decode_bc: Optional[int] = None


# ---------------------------------------------------------------------------
# layer kinds per architecture family
# ---------------------------------------------------------------------------

def layer_kinds(cfg: ModelConfig) -> Tuple[str, ...]:
    if cfg.family == "ssm":
        return ssm_lib.xlstm_layer_kinds(cfg)
    if cfg.family == "hybrid":
        return rglru_lib.rglru_block_pattern(cfg)
    moe_set = set(cfg.moe_layer_indices())
    return tuple("attn_moe" if i in moe_set else "attn_mlp"
                 for i in range(cfg.num_layers))


def pattern_group(cfg: ModelConfig) -> Tuple[str, ...]:
    """Smallest repeating unit of layer kinds (for scanned stacking)."""
    kinds = layer_kinds(cfg)
    for size in range(1, len(kinds) + 1):
        if len(kinds) % size == 0 and kinds == kinds[:size] * (len(kinds) // size):
            return kinds[:size]
    return kinds


# ---------------------------------------------------------------------------
# single layer init/apply
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig, kind: str,
               num_experts_padded: int = 0, cross: bool = False):
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {}
    if kind in ("attn_mlp", "attn_moe"):
        p["ln1"] = rmsnorm_init(cfg.d_model)
        p["attn"] = attn.attention_init(ks[0], cfg)
        p["ln2"] = rmsnorm_init(cfg.d_model)
        if kind == "attn_mlp":
            p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.ffn_dim)
        else:
            p["moe"] = moe_lib.moe_init(ks[1], cfg.d_model, cfg.moe,
                                        num_experts_padded)
        if cross:
            p["ln_x"] = rmsnorm_init(cfg.d_model)
            p["cross"] = attn.cross_attention_init(ks[2], cfg)
    elif kind == "mlstm":
        p["ln1"] = rmsnorm_init(cfg.d_model)
        p["mlstm"] = ssm_lib.mlstm_init(ks[0], cfg)
    elif kind == "slstm":
        p["ln1"] = rmsnorm_init(cfg.d_model)
        p["slstm"] = ssm_lib.slstm_init(ks[0], cfg)
    elif kind == "rec":
        p["ln1"] = rmsnorm_init(cfg.d_model)
        p["rglru"] = rglru_lib.rglru_init(ks[0], cfg)
        p["ln2"] = rmsnorm_init(cfg.d_model)
        p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.ffn_dim)
    elif kind == "attn":  # hybrid local-attention block
        p["ln1"] = rmsnorm_init(cfg.d_model)
        p["attn"] = attn.attention_init(ks[0], cfg)
        p["ln2"] = rmsnorm_init(cfg.d_model)
        p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.ffn_dim)
    else:
        raise ValueError(kind)
    return p


def init_layer_cache(cfg: ModelConfig, kind: str, batch: int, seq_len: int,
                     dtype=jnp.bfloat16):
    if kind in ("attn_mlp", "attn_moe", "attn"):
        cap = attn.cache_capacity(cfg, seq_len)
        return attn.init_kv_cache(cfg, batch, cap, dtype)
    if kind == "mlstm":
        return ssm_lib.mlstm_state(cfg, batch)
    if kind == "slstm":
        return ssm_lib.slstm_state(cfg, batch)
    if kind == "rec":
        return rglru_lib.rglru_state(cfg, batch)
    raise ValueError(kind)


def _apply_moe(p, cfg: ModelConfig, h, ctx: ExecutionContext,
               num_experts_padded: int, plan=None, placement=None,
               collect_stats: bool = False, capacity_scale: float = 1.0):
    """Returns (y, aux), or (y, aux, moe.MoEStats) with
    ``collect_stats``. ``placement`` (a ``repro.placement.Placement``)
    and ``capacity_scale`` (skew-aware dispatch-buffer widening) only
    reach the DEP path — the single-device impls execute the logical
    layout directly."""
    if ctx.moe_impl == "dense":
        return moe_lib.moe_apply_dense(p["moe"], h, cfg.moe,
                                       num_experts_padded,
                                       return_stats=collect_stats)
    if ctx.moe_impl == "capacity":
        return moe_lib.moe_apply_capacity(p["moe"], h, cfg.moe,
                                          num_experts_padded,
                                          return_stats=collect_stats)
    if ctx.moe_impl == "dep":
        from repro.core import dep as dep_lib
        return dep_lib.moe_apply_dep(p["moe"], h, cfg.moe, ctx,
                                     num_experts_padded, plan=plan,
                                     placement=placement,
                                     return_stats=collect_stats,
                                     capacity_scale=capacity_scale)
    raise ValueError(ctx.moe_impl)


def apply_layer(p, cfg: ModelConfig, kind: str, x, positions,
                cache, mode: str, ctx: ExecutionContext,
                num_experts_padded: int = 0, memory=None, plan=None,
                lengths=None, block_table=None, placement=None,
                stats_sink=None, capacity_scale: float = 1.0):
    """Returns (x, new_cache, aux_loss). ``lengths`` is the decode-mode
    per-slot KV ledger vector, shared by every attention layer;
    ``block_table`` is the decode-mode paged-KV page map (also shared —
    one table addresses every layer's page pool). ``stats_sink`` is an
    optional Python list MoE layers append their ``moe.MoEStats`` to
    (load telemetry; Python-loop layer paths only)."""
    aux = jnp.zeros((), jnp.float32)
    local_cfg = cfg
    if kind == "attn" and cfg.family == "hybrid":
        local_cfg = dataclasses.replace(cfg, attention="local")

    if kind in ("attn_mlp", "attn_moe", "attn"):
        h = rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
        with jax.named_scope("attention"):
            if mode == "decode":
                a, cache = attn.attention_decode(
                    p["attn"], local_cfg, h, cache, impl=ctx.attn_impl,
                    ctx=ctx, lengths=lengths, block_table=block_table)
            else:
                a, cache = attn.attention_fullseq(p["attn"], local_cfg, h,
                                                  positions, cache,
                                                  impl=ctx.attn_impl)
        x = x + a
        if memory is not None and "cross" in p:
            hx = rmsnorm_apply(p["ln_x"], x, cfg.norm_eps)
            x = x + attn.cross_attention_apply(p["cross"], cfg, hx, memory)
        h = rmsnorm_apply(p["ln2"], x, cfg.norm_eps)
        if kind == "attn_moe":
            if stats_sink is not None:
                y, aux, st = _apply_moe(p, cfg, h, ctx, num_experts_padded,
                                        plan, placement, collect_stats=True,
                                        capacity_scale=capacity_scale)
                stats_sink.append(st)
            else:
                y, aux = _apply_moe(p, cfg, h, ctx, num_experts_padded,
                                    plan, placement,
                                    capacity_scale=capacity_scale)
        else:
            y = mlp_apply(p["mlp"], h)
        return x + y, cache, aux

    if kind in ("mlstm", "slstm"):
        h = rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
        fn = ssm_lib.mlstm_apply if kind == "mlstm" else ssm_lib.slstm_apply
        if cache is None:
            cache = init_layer_cache(cfg, kind, x.shape[0], 0)
        y, cache = fn(p[kind], cfg, h, cache)
        return x + y, cache, aux

    if kind == "rec":
        h = rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
        if cache is None:
            cache = rglru_lib.rglru_state(cfg, x.shape[0])
        if mode == "decode":
            y, cache = rglru_lib.rglru_step(p["rglru"], cfg, h, cache)
        else:
            y, cache = rglru_lib.rglru_apply(p["rglru"], cfg, h, cache)
        x = x + y
        h = rmsnorm_apply(p["ln2"], x, cfg.norm_eps)
        return x + mlp_apply(p["mlp"], h), cache, aux

    raise ValueError(kind)


def _stack_moe_stats(sink):
    """Collapse a stats_sink list into one ``moe.MoEStats`` with
    ``load`` stacked to [L_moe, E] and ``dropped`` summed over layers.
    Returns None for an empty sink (no MoE layers, or scan_layers)."""
    sink = [s for s in sink if s is not None]
    if not sink:
        return None
    return moe_lib.MoEStats(
        load=jnp.stack([s.load for s in sink]),
        dropped=functools.reduce(jnp.add, [s.dropped for s in sink]))


# ---------------------------------------------------------------------------
# the Model
# ---------------------------------------------------------------------------

class Model:
    """Causal LM (all families); encoder-decoder when cfg.is_encoder_decoder."""

    def __init__(self, cfg: ModelConfig, ctx: Optional[ExecutionContext] = None,
                 num_experts_padded: int = 0, scan_layers: bool = False,
                 dtype=jnp.bfloat16, plan=None):
        self.cfg = cfg
        self.ctx = ctx or ExecutionContext()
        # default schedule for static pipelines (dry-runs, training); the
        # serving engine overrides it per call with policy-resolved plans
        self.plan = plan
        self.E_pad = num_experts_padded or (cfg.moe.num_experts if cfg.moe else 0)
        self.scan_layers = scan_layers
        self.dtype = dtype
        self.kinds = layer_kinds(cfg)
        self.group = pattern_group(cfg)
        self.num_groups = len(self.kinds) // len(self.group)

    # ---- init -----------------------------------------------------------
    def init(self, key, dtype=None, shardings=None) -> Dict[str, Any]:
        """Float32 parameters. With ``dtype``, the same values cast to
        it: each layer is built and cast under a jit compiled once per
        layer kind and called per layer, so no float32 copy of the whole
        model is ever live and the compile does not grow with depth.
        ``shardings`` (a tree like the result; layers of one kind share a
        placement) places each array as it is made."""
        cfg = self.cfg
        cross = cfg.is_encoder_decoder
        jits: Dict[Any, Callable] = {}

        def make(name, fn, k, sharding_of):
            if dtype is None:
                return fn(k)
            if name not in jits:
                jits[name] = jax.jit(
                    lambda kk: jax.tree.map(lambda a: a.astype(dtype),
                                            fn(kk)),
                    out_shardings=(sharding_of(shardings)
                                   if shardings is not None else None))
            return jits[name](k)

        def top(keys):
            p: Dict[str, Any] = {
                "embed": embedding_init(keys[0], cfg.vocab_size, cfg.d_model),
                "final_norm": rmsnorm_init(cfg.d_model),
            }
            if not cfg.tie_embeddings:
                p["lm_head"] = dense_init(keys[1], cfg.d_model,
                                          cfg.vocab_size)
            return p

        keys = jax.random.split(key, 8)
        params = make("top", top, keys,
                      lambda s: {n: s[n] for n in ("embed", "final_norm",
                                                   "lm_head") if n in s})
        if self.scan_layers:
            def init_group(gkey):
                gks = jax.random.split(gkey, len(self.group))
                return [init_layer(gks[i], cfg, kind, self.E_pad, cross)
                        for i, kind in enumerate(self.group)]

            def groups(k):
                return jax.vmap(init_group)(
                    jax.random.split(k, self.num_groups))
            params["layer_groups"] = make("groups", groups, keys[2],
                                          lambda s: s["layer_groups"])
        else:
            lkeys = jax.random.split(keys[2], len(self.kinds))
            params["layers"] = [
                make(("layer", kind),
                     functools.partial(init_layer, cfg=cfg, kind=kind,
                                       num_experts_padded=self.E_pad,
                                       cross=cross),
                     lkeys[i], lambda s, i=i: s["layers"][i])
                for i, kind in enumerate(self.kinds)]
        if cfg.is_encoder_decoder:
            ekeys = jax.random.split(keys[3], cfg.num_encoder_layers + 1)
            params["enc_layers"] = [
                make("enc_layer",
                     functools.partial(init_layer, cfg=cfg, kind="attn_mlp"),
                     ekeys[i], lambda s, i=i: s["enc_layers"][i])
                for i in range(cfg.num_encoder_layers)]
            params["enc_norm"] = make(
                "enc_norm", lambda _: rmsnorm_init(cfg.d_model), keys[3],
                lambda s: s["enc_norm"])
        if cfg.family == "vlm":
            params["proj"] = make(
                "proj", lambda k: dense_init(k, cfg.d_model, cfg.d_model),
                keys[4], lambda s: s["proj"])
        return params

    # ---- encoder (audio family) ------------------------------------------
    def encode(self, params, frame_embeds):
        """frame_embeds: [B, S_enc, M] from the (stubbed) modality frontend."""
        cfg = self.cfg
        x = frame_embeds.astype(self.dtype)
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2])
        bidir_cfg = dataclasses.replace(cfg, attention="full")
        for p in params["enc_layers"]:
            h = rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
            a = _encoder_self_attention(p["attn"], bidir_cfg, h, positions)
            x = x + a
            h = rmsnorm_apply(p["ln2"], x, cfg.norm_eps)
            x = x + mlp_apply(p["mlp"], h)
        return rmsnorm_apply(params["enc_norm"], x, cfg.norm_eps)

    # ---- embeddings -------------------------------------------------------
    def _embed_inputs(self, params, tokens, extra_embeds):
        x = embedding_apply(params["embed"], tokens, self.dtype)
        if extra_embeds is not None and self.cfg.family == "vlm":
            vis = dense_apply(params["proj"], extra_embeds.astype(self.dtype))
            x = jnp.concatenate([vis, x], axis=1)
        return x

    # ---- full-sequence forward -------------------------------------------
    def forward(self, params, tokens, extra_embeds=None, memory=None,
                caches=None, plan=None, placement=None, stats_sink=None,
                capacity_scale: float = 1.0):
        """tokens: [B, S]. extra_embeds: vlm patch embeds [B, P, M].
        memory: encoder output for enc-dec. caches: list to fill (prefill).
        plan: per-call schedule for DEP MoE layers (defaults to the model's
        static plan); placement: active expert ``Placement`` for the DEP
        path; stats_sink: optional list collecting per-MoE-layer
        ``moe.MoEStats`` (Python-loop path only — scanned layers skip
        collection). Returns (logits, new_caches, aux)."""
        x, new_caches, aux_total = self._hidden(
            params, tokens, extra_embeds, memory, caches, plan, placement,
            stats_sink, capacity_scale)
        return self._head(params, x), new_caches, aux_total

    def _hidden(self, params, tokens, extra_embeds, memory, caches, plan,
                placement, stats_sink, capacity_scale):
        """The layers of ``forward``: (hidden states [B, S, M] before the
        final norm, new_caches, aux)."""
        cfg = self.cfg
        plan = plan if plan is not None else self.plan
        if cfg.is_encoder_decoder and memory is None and extra_embeds is not None:
            memory = self.encode(params, extra_embeds)
            extra_embeds = None
        x = self._embed_inputs(params, tokens, extra_embeds)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
        aux_total = jnp.zeros((), jnp.float32)
        new_caches = [None] * len(self.kinds)
        if self.scan_layers:
            stats_sink = None               # no per-layer sink under scan

        def layer_fn(p, kind, x, cache):
            return apply_layer(p, cfg, kind, x, positions, cache, "forward",
                               self.ctx, self.E_pad, memory, plan,
                               placement=placement, stats_sink=stats_sink,
                               capacity_scale=capacity_scale)

        if self.scan_layers:
            x, new_caches, aux_total = self._scan_groups(
                params, x, caches, layer_fn)
        else:
            for i, kind in enumerate(self.kinds):
                cache = caches[i] if caches is not None else None
                fn = layer_fn
                if self.ctx.remat:
                    fn = jax.checkpoint(layer_fn, static_argnums=(1,))
                x, new_caches[i], aux = fn(params["layers"][i], kind, x, cache)
                aux_total = aux_total + aux
        return x, new_caches, aux_total

    def _head(self, params, x):
        """Final norm and output head: float32 logits of hidden rows."""
        x = rmsnorm_apply(params["final_norm"], x, self.cfg.norm_eps)
        return self._readout(params, x)

    def _scan_groups(self, params, x, caches, layer_fn):
        """lax.scan over stacked pattern groups."""
        gsize = len(self.group)
        stacked_caches = caches  # already stacked by init_cache(scan=True)

        def body(carry, inputs):
            x, aux = carry
            gparams, gcaches = inputs
            new_gcaches = []
            for j, kind in enumerate(self.group):
                c = gcaches[j] if gcaches is not None else None
                x, nc, a = layer_fn(gparams[j], kind, x, c)
                new_gcaches.append(nc)
                aux = aux + a
            return (x, aux), new_gcaches

        body_fn = body
        if self.ctx.remat:
            body_fn = jax.checkpoint(body)
        (x, aux), new_caches = jax.lax.scan(
            body_fn, (x, jnp.zeros((), jnp.float32)),
            (params["layer_groups"], stacked_caches))
        return x, new_caches, aux

    def _readout(self, params, x):
        """Float32 logits, accumulated in float32 from operands in the
        head's own dtype (no float32 copy of a bf16 head)."""
        with jax.named_scope("lm_head"):
            if self.cfg.tie_embeddings:
                return embedding_attend(params["embed"], x)
            w = params["lm_head"]["kernel"]
            return jnp.dot(x.astype(w.dtype), w,
                           preferred_element_type=jnp.float32)

    # ---- expert rows -------------------------------------------------------
    @property
    def routed_per_token(self) -> int:
        """Token-expert assignments one token makes over all MoE layers."""
        n = self.kinds.count("attn_moe")
        return n * self.cfg.moe.top_k if n else 0

    def expert_rows(self, batch: int, seq: int, plan=None,
                    capacity_scale: float = 1.0) -> int:
        """Rows the routed-expert FFN computes over all MoE layers for a
        [batch, seq] call, padding included, as ``moe_impl`` sizes them:
        every expert on every token on the dense path, the dispatch
        buffers' E_pad x capacity on the capacity and DEP paths."""
        n = self.kinds.count("attn_moe")
        if not n:
            return 0
        if self.ctx.moe_impl == "dep":
            from repro.core import dep as dep_lib
            return n * dep_lib.expert_rows(batch, seq, self.cfg.moe, self.ctx,
                                           self.E_pad, plan, capacity_scale)
        return n * moe_lib.expert_rows(batch * seq, self.cfg.moe, self.E_pad,
                                       self.ctx.moe_impl)

    # ---- caches ------------------------------------------------------------
    def init_cache(self, batch: int, seq_len: int, dtype=jnp.bfloat16):
        if self.scan_layers:
            def one_group(_):
                return [init_layer_cache(self.cfg, kind, batch, seq_len, dtype)
                        for kind in self.group]
            return jax.vmap(one_group)(jnp.arange(self.num_groups))
        return [init_layer_cache(self.cfg, kind, batch, seq_len, dtype)
                for kind in self.kinds]

    # ---- prefill / decode ---------------------------------------------------
    def prefill(self, params, tokens, extra_embeds=None, memory=None,
                seq_budget: Optional[int] = None, cache_dtype=None,
                plan=None, last_positions=None, placement=None,
                return_moe_stats: bool = False,
                capacity_scale: float = 1.0):
        """tokens: [B, S] (right-padded when batching multiple requests).
        ``last_positions`` ([B] int, optional) gathers each row's logits
        at its own last REAL token instead of the padded bucket end —
        the batched multi-request prefill path, where rows share one
        bucket but differ in true prompt length. ``return_moe_stats``
        appends a stacked ``moe.MoEStats`` ([L_moe, E] loads + total
        dropped count; None under scan_layers) to the return."""
        B, S = tokens.shape
        budget = seq_budget or S
        off = 0
        if extra_embeds is not None and self.cfg.family == "vlm":
            budget += extra_embeds.shape[1]     # image tokens share the cache
            off = extra_embeds.shape[1]         # logits include image slots
        caches = self.init_cache(B, budget, cache_dtype or self.dtype)
        sink = [] if return_moe_stats else None
        x, caches, _ = self._hidden(params, tokens, extra_embeds, memory,
                                    caches, plan, placement, sink,
                                    capacity_scale)
        # the head runs on the returned rows only: the other S-1 rows'
        # [B, S, V] float32 logits would be computed and thrown away
        if last_positions is not None:
            pos = jnp.asarray(last_positions, jnp.int32) + off
            x = x[jnp.arange(B), pos][:, None]              # [B, 1, M]
        else:
            x = x[:, -1:]
        last = self._head(params, x)                        # [B, 1, V]
        if return_moe_stats:
            return last, caches, _stack_moe_stats(sink)
        return last, caches

    def decode_step(self, params, tokens, caches, memory=None, plan=None,
                    lengths=None, block_tables=None, placement=None,
                    return_moe_stats: bool = False,
                    capacity_scale: float = 1.0):
        """tokens: [B, 1] -> (logits [B,1,V], new caches), plus a stacked
        ``moe.MoEStats`` when ``return_moe_stats`` (None under
        scan_layers, where the per-layer Python sink cannot run).

        ``lengths`` ([B] int, optional): per-slot context lengths from the
        KV ledger — computed once by the engine and shared by every
        attention layer (mask source + ragged-kernel block skip) instead
        of being recomputed per layer from each cache index.
        ``block_tables`` (int [B, max_blocks], optional): paged-KV page
        map; ONE table serves every attention layer, since page p of each
        layer's pool belongs to the same logical block. None = dense."""
        cfg = self.cfg
        plan = plan if plan is not None else self.plan
        x = embedding_apply(params["embed"], tokens, self.dtype)
        aux = jnp.zeros((), jnp.float32)
        positions = None  # decode positions come from cache index
        sink = ([] if (return_moe_stats and not self.scan_layers) else None)

        def layer_fn(p, kind, x, cache):
            return apply_layer(p, cfg, kind, x, positions, cache, "decode",
                               self.ctx, self.E_pad, memory, plan,
                               lengths=lengths, block_table=block_tables,
                               placement=placement, stats_sink=sink,
                               capacity_scale=capacity_scale)

        if self.scan_layers:
            x, new_caches, aux = self._scan_groups(params, x, caches, layer_fn)
        else:
            new_caches = []
            for i, kind in enumerate(self.kinds):
                x, nc, a = layer_fn(params["layers"][i], kind, x, caches[i])
                new_caches.append(nc)
                aux = aux + a
        logits = self._head(params, x)
        if return_moe_stats:
            return logits, new_caches, _stack_moe_stats(sink or [])
        return logits, new_caches

    # ---- loss ----------------------------------------------------------------
    def loss(self, params, tokens, extra_embeds=None, ce_chunk: int = 512,
             plan=None):
        """Next-token CE (shift-by-one) + MoE aux loss.

        Uses a chunked fused linear+softmax-xent: the [tokens, vocab] f32
        logits are never materialized in full (vocab up to 256k makes the
        full tensor the dominant training-memory term); each sequence chunk
        is projected, reduced and rematerialized in the backward pass.
        """
        cfg = self.cfg
        plan = plan if plan is not None else self.plan
        memory = None
        if cfg.is_encoder_decoder and extra_embeds is not None:
            memory = self.encode(params, extra_embeds)
            extra_embeds = None
        x = self._embed_inputs(params, tokens, extra_embeds)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
        aux_total = jnp.zeros((), jnp.float32)

        def layer_fn(p, kind, x, cache):
            return apply_layer(p, cfg, kind, x, positions, cache, "forward",
                               self.ctx, self.E_pad, memory, plan)

        if self.scan_layers:
            x, _, aux_total = self._scan_groups(params, x, None, layer_fn)
        else:
            for i, kind in enumerate(self.kinds):
                fn = layer_fn
                if self.ctx.remat:
                    fn = jax.checkpoint(layer_fn, static_argnums=(1,))
                x, _, aux = fn(params["layers"][i], kind, x, None)
                aux_total = aux_total + aux

        x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        off = (extra_embeds.shape[1]
               if (extra_embeds is not None and cfg.family == "vlm") else 0)
        pred = x[:, off:off + tokens.shape[1] - 1]
        tgt = tokens[:, 1:]
        if cfg.tie_embeddings:
            W = params["embed"]["embedding"].T
        else:
            W = params["lm_head"]["kernel"]
        nll_mean = chunked_softmax_xent(pred, W, tgt, chunk=ce_chunk)
        coef = cfg.moe.router_aux_loss_coef if cfg.moe else 0.0
        return nll_mean + coef * aux_total


def chunked_softmax_xent(x, readout, targets, chunk: int = 512):
    """Fused linear + softmax cross-entropy over sequence chunks.

    x: [B, T, M] final hidden states; readout: [M, V]; targets: [B, T].
    Never materializes more than [B, chunk, V] of logits; each chunk is
    jax.checkpoint'ed so backward recomputes its logits.
    Returns mean NLL over all B*T positions.
    """
    B, T, M = x.shape
    n = max((T + chunk - 1) // chunk, 1)
    Tp = n * chunk
    if Tp != T:
        x = jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, Tp - T)))
    mask = (jnp.arange(Tp) < T).astype(jnp.float32)         # [Tp]
    xs = x.reshape(B, n, chunk, M).swapaxes(0, 1)           # [n,B,c,M]
    ts = targets.reshape(B, n, chunk).swapaxes(0, 1)
    ms = mask.reshape(n, chunk)

    @jax.checkpoint
    def body(carry, inp):
        xc, tc, mc = inp
        logits = (xc.astype(jnp.float32)
                  @ readout.astype(jnp.float32))            # [B,c,V]
        lse = jax.nn.logsumexp(logits, axis=-1)
        # vocab-parallel gold logit: take_along_axis over a vocab-sharded
        # logits tensor makes GSPMD all-gather the FULL [B,c,V] f32 logits
        # (~1 TB for 256k vocab at train_4k); a one-hot masked reduction is
        # elementwise over the sharded dim and reduces with a psum instead.
        onehot = (jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
                  == tc[..., None])
        gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
        return carry + jnp.sum((lse - gold) * mc[None, :]), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ts, ms))
    return total / (B * T)


def _encoder_self_attention(p, cfg: ModelConfig, h, positions):
    """Bidirectional self-attention for the encoder stack."""
    B, S, _ = h.shape
    hd = cfg.head_dim
    q = dense_apply(p["wq"], h).reshape(B, S, cfg.num_heads, hd)
    k = dense_apply(p["wk"], h).reshape(B, S, cfg.num_kv_heads, hd)
    v = dense_apply(p["wv"], h).reshape(B, S, cfg.num_kv_heads, hd)
    q = attn.apply_rope(q, positions, cfg.rope_theta)
    k = attn.apply_rope(k, positions, cfg.rope_theta)
    mask = jnp.ones((S, S), bool)
    out = attn._sdpa(q, k, v, mask)
    return dense_apply(p["wo"], out.reshape(B, S, -1))
