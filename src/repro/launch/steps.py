"""Step builders + abstract input specs for every (arch × input shape).

This is the single source used by the dry-run, the training loop and the
examples:

  * ``build(arch_cfg, shape, mesh)``   -> StepBundle with jit-able step fn,
    abstract inputs (ShapeDtypeStruct, no allocation) and shardings.
  * train shapes lower ``train_step``  (loss + grads + AdamW update)
  * prefill shapes lower ``prefill_step``
  * decode shapes lower ``serve_step`` (ONE new token against a KV cache of
    seq_len capacity — ring-buffer capped for sliding/local attention)
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import build_model, frontend_shape
from repro.models.transformer import ExecutionContext, Model
from repro.sharding.partition import (batch_pspec, cache_pspecs,
                                      params_pspecs)
from repro.training.optimizer import AdamWConfig, OptState, apply_updates, \
    init_opt_state


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def experts_padded(cfg: ModelConfig, mesh: Optional[Mesh],
                   model_axis: str = "model") -> int:
    if cfg.moe is None:
        return 0
    if mesh is None:
        return cfg.moe.num_experts
    return round_up(cfg.moe.num_experts, mesh.shape[model_axis])


LONG_CONTEXT_WINDOW = 8192


def adapt_for_shape(cfg: ModelConfig, shape: ShapeConfig) -> ModelConfig:
    """long_500k decode requires sub-quadratic attention: SSM/hybrid/sliding
    archs run natively; full-attention archs switch to the sliding-window
    decode variant (ring KV cache, window 8192) — see DESIGN.md §4."""
    if (shape.name == "long_500k" and shape.mode == "decode"
            and cfg.uses_attention and not cfg.subquadratic):
        return dataclasses.replace(cfg, attention="sliding",
                                   sliding_window=LONG_CONTEXT_WINDOW,
                                   mla_kv_lora_rank=0, rope_scaling=None)
    return cfg


def pattern_len(cfg: ModelConfig) -> int:
    from repro.models.transformer import pattern_group
    try:
        return len(pattern_group(cfg))
    except Exception:
        return 1


def probe_config(cfg: ModelConfig, units: int) -> ModelConfig:
    """Reduced-depth variant for count-accurate probing: ``units`` repeats
    of the layer pattern, encoder scaled proportionally. All other dims are
    the full config's — counts are linear in units, so two probes determine
    the exact (intercept, slope) for the full depth."""
    plen = pattern_len(cfg)
    kw = dict(num_layers=units * plen)
    if cfg.is_encoder_decoder and cfg.num_encoder_layers:
        ratio = cfg.num_encoder_layers / cfg.num_layers
        kw["num_encoder_layers"] = max(1, round(units * plen * ratio))
    return dataclasses.replace(cfg, **kw)


def default_accum_steps(cfg: ModelConfig, shape: ShapeConfig,
                        mesh: Optional[Mesh],
                        target_tokens_per_device: int = 16384) -> int:
    """Micro-batch count bounding per-device activation tokens."""
    if shape.mode != "train" or mesh is None:
        return 1
    from repro.sharding.partition import batch_pspec
    spec = batch_pspec(shape.global_batch, mesh)
    dp = 1
    if spec != P(None):
        entry = spec[0]
        axes = (entry,) if isinstance(entry, str) else (entry or ())
        for a in axes:
            dp *= mesh.shape[a]
    tokens_per_dev = shape.global_batch // dp * shape.seq_len
    want = max(1, tokens_per_dev // target_tokens_per_device)
    # largest accum <= want that divides the per-device batch
    b_dev = shape.global_batch // dp
    accum = 1
    for k in range(1, b_dev + 1):
        if b_dev % k == 0 and k <= want:
            accum = k
    return accum


@dataclass
class StepBundle:
    name: str
    step_fn: Callable                  # (params[, opt_state], **inputs)
    abstract_args: Tuple               # ShapeDtypeStructs, jit-order args
    in_shardings: Tuple
    out_shardings: Any
    model: Model
    donate_argnums: Tuple[int, ...] = ()
    accum_steps: int = 1

    def lower(self):
        return jax.jit(self.step_fn,
                       in_shardings=self.in_shardings,
                       out_shardings=self.out_shardings,
                       donate_argnums=self.donate_argnums,
                       ).lower(*self.abstract_args)


def _sds(shape, dtype, mesh=None, spec=None):
    if mesh is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec or P()))


def _with_sharding(tree, spec_tree, mesh):
    return jax.tree.map(
        lambda sds, spec: jax.ShapeDtypeStruct(
            sds.shape, sds.dtype, sharding=NamedSharding(mesh, spec)),
        tree, spec_tree)


def cluster_from_mesh(mesh: Mesh, dtype_bytes: int = 2,
                      model_axis: str = "model"):
    """Map a launch mesh onto the planner's DEP cluster view: the expert
    group is the ``model`` axis (experts are expert-parallel over it, see
    repro.core.dep) and the attention group is the data-parallel extent."""
    from repro.configs.base import DepClusterConfig
    shape = dict(mesh.shape)
    n = mesh.size
    if n < 2:
        raise ValueError("DEP needs >= 2 devices (an attention group AND "
                         f"an expert group); mesh has {n}")
    # a mesh whose model axis spans every device leaves no room for the
    # attention group under the cluster's disjoint-groups accounting
    eg = min(shape.get(model_axis, 1), n - 1)
    ag = max(min(n // eg, n - eg), 1)
    return DepClusterConfig(num_devices=n, ag=ag, eg=eg,
                            dtype_bytes=dtype_bytes)


def launch_policy(cfg: ModelConfig, mesh: Mesh, policy: str = "findep",
                  profile=None, mem_cap_samples: int = 64,
                  static_seq_len: Optional[int] = None,
                  profile_store=None):
    """Build a ``repro.sched`` SchedulePolicy for a static launch pipeline
    by name, so dry-runs and step builders can plan per shape instead of
    demanding an explicit frozen plan (ROADMAP follow-up).

    ``profile`` is a HardwareProfile, a registry name, or a name stored in
    ``profile_store`` (a repro.profiling.ProfileStore or its root path) —
    i.e. a calibrated fit from ``examples/serve_moe.py --calibrate``.
    Defaults to the TPU v5e analytic profile, the launch target."""
    from repro.core.perf_model import (HardwareProfile, TPU_V5E, get_profile)
    from repro.core.planner import FinDEPPlanner, PlannerConfig
    from repro.sched import make_policy
    if isinstance(profile, HardwareProfile):
        hw = profile
    elif profile is None:
        hw = TPU_V5E
    else:
        hw = None
        if profile_store is not None:
            from repro.profiling import ProfileStore
            store = (profile_store
                     if isinstance(profile_store, ProfileStore)
                     else ProfileStore(profile_store))
            try:
                hw = store.load_profile(profile)
            except KeyError:
                hw = None
        if hw is None:
            hw = get_profile(profile)
    planner = FinDEPPlanner(cfg, cluster_from_mesh(mesh), hw,
                            PlannerConfig(mem_cap_samples=mem_cap_samples))
    return make_policy(policy, planner, static_seq_len=static_seq_len)


def resolve_launch_plan(cfg: ModelConfig, mesh: Optional[Mesh],
                        policy, seq_len: int, mode: str = "prefill",
                        batch_per_device: Optional[int] = None,
                        profile=None, profile_store=None):
    """Resolve the schedule a static pipeline should compile for one
    shape. ``policy`` is a SchedulePolicy or a name ("findep" etc.);
    returns None when the config/mesh cannot be DEP-scheduled."""
    if not cfg.is_moe or mesh is None:
        return None
    if isinstance(policy, str):
        policy = launch_policy(cfg, mesh, policy, profile=profile,
                               static_seq_len=seq_len,
                               profile_store=profile_store)
    phase = "decode" if mode == "decode" else "prefill"
    return policy.resolve(phase, seq_len, batch_per_device)


def make_model(cfg: ModelConfig, mesh: Optional[Mesh] = None,
               plan=None, scan_layers: Optional[bool] = None,
               moe_impl: Optional[str] = None, remat: bool = False,
               policy=None, seq_len: Optional[int] = None,
               batch_per_device: Optional[int] = None, profile=None,
               interleave: str = "streams",
               dtype=jnp.bfloat16) -> Model:
    if scan_layers is None:
        scan_layers = cfg.num_layers > 8
    if moe_impl is None:
        moe_impl = "dep" if (mesh is not None and cfg.is_moe) else "capacity"
    data_axes = (tuple(a for a in mesh.axis_names if a != "model")
                 if mesh is not None else ("data",))
    ctx = ExecutionContext(mesh=mesh, moe_impl=moe_impl,
                           remat=remat, data_axes=data_axes)
    if plan is None and policy is not None:
        if seq_len is None:
            raise ValueError("make_model(policy=...) needs seq_len — the "
                             "shape the compiled schedule is for")
        plan = resolve_launch_plan(cfg, mesh, policy, seq_len,
                                   batch_per_device=batch_per_device,
                                   profile=profile)
    # static pipelines compile one schedule per shape: the resolved plan is
    # lowered here to the ExecProgram the DEP walker consumes, so the
    # emission policy (r1-stream interleaving + priority hints) is fixed at
    # build time alongside the schedule itself
    if plan is not None and hasattr(plan, "exec_program"):
        plan = plan.exec_program(interleave=interleave)
    return build_model(cfg, ctx=ctx,
                       num_experts_padded=experts_padded(cfg, mesh),
                       scan_layers=scan_layers, dtype=dtype, plan=plan)


def abstract_params(model: Model, dtype=jnp.bfloat16):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype
                                       if s.dtype == jnp.float32 else s.dtype),
        shapes)


# ---------------------------------------------------------------------------
# input specs per mode
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig,
                mesh: Optional[Mesh] = None, dtype=jnp.bfloat16
                ) -> Dict[str, Any]:
    """Abstract model inputs (ShapeDtypeStruct) for one input shape."""
    B, S = shape.global_batch, shape.seq_len
    bspec = batch_pspec(B, mesh) if mesh is not None else None

    def sds(shp, dt, spec):
        return _sds(shp, dt, mesh, spec)

    specs: Dict[str, Any] = {}
    if shape.mode in ("train", "prefill"):
        specs["tokens"] = sds((B, S), jnp.int32,
                              P(*(bspec or P(None)), None) if mesh else None)
    else:
        specs["tokens"] = sds((B, 1), jnp.int32,
                              P(*(bspec or P(None)), None) if mesh else None)
    fs = frontend_shape(cfg, shape)
    if fs is not None:
        if cfg.is_encoder_decoder and shape.mode == "decode":
            # decode consumes precomputed encoder memory
            specs["memory"] = sds((B, fs[1], cfg.d_model), dtype,
                                  P(*(bspec or P(None)), None, None)
                                  if mesh else None)
        else:
            specs["extra"] = sds(fs, dtype,
                                 P(*(bspec or P(None)), None, None)
                                 if mesh else None)
    return specs


def decode_cache_specs(model: Model, cfg: ModelConfig, shape: ShapeConfig,
                       mesh: Optional[Mesh], dtype=jnp.bfloat16):
    B, S = shape.global_batch, shape.seq_len
    budget = S
    if cfg.family == "vlm":
        budget += cfg.frontend_tokens
    cache_shapes = jax.eval_shape(
        partial(model.init_cache, B, budget, dtype))
    if mesh is None:
        return cache_shapes
    pspecs = cache_pspecs(cache_shapes, cfg, mesh, B,
                          stacked=model.scan_layers)
    return _with_sharding(cache_shapes, pspecs, mesh)


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------

def make_train_step(model: Model, opt_cfg: Optional[AdamWConfig] = None,
                    accum_steps: int = 1, accum_dtype=jnp.float32,
                    ce_chunk: Optional[int] = None):
    """Gradient-accumulating train step: the global batch is split into
    ``accum_steps`` micro-batches scanned with value_and_grad inside the
    body, bounding peak activation memory to one micro-batch."""
    opt_cfg = opt_cfg or AdamWConfig(state_dtype=jnp.bfloat16)

    def train_step(params, opt_state, tokens, extra=None):
        def loss_fn(p, tok, ex):
            if ce_chunk is not None:
                return model.loss(p, tok, extra_embeds=ex,
                                  ce_chunk=ce_chunk)
            return model.loss(p, tok, extra_embeds=ex)

        if accum_steps <= 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens, extra)
        else:
            B = tokens.shape[0]
            mb = B // accum_steps
            tok_mb = tokens.reshape(accum_steps, mb, *tokens.shape[1:])
            ex_mb = (extra.reshape(accum_steps, mb, *extra.shape[1:])
                     if extra is not None else None)

            def body(carry, inp):
                g_acc, loss_acc = carry
                tok_i = inp[0]
                ex_i = inp[1] if extra is not None else None
                loss_i, g_i = jax.value_and_grad(loss_fn)(params, tok_i, ex_i)
                g_acc = jax.tree.map(
                    lambda a, g: a + g.astype(accum_dtype), g_acc, g_i)
                return (g_acc, loss_acc + loss_i), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, accum_dtype), params)
            xs = (tok_mb, ex_mb) if extra is not None else (tok_mb,)
            (g_sum, loss_sum), _ = jax.lax.scan(
                body, (g0, jnp.zeros((), jnp.float32)), xs)
            grads = jax.tree.map(lambda g: g / accum_steps, g_sum)
            loss = loss_sum / accum_steps
        new_p, new_s, gnorm = apply_updates(params, grads, opt_state,
                                            opt_cfg)
        return new_p, new_s, {"loss": loss, "grad_norm": gnorm}

    return train_step


def make_prefill_step(model: Model):
    def prefill_step(params, tokens, extra=None):
        memory = None
        if model.cfg.is_encoder_decoder and extra is not None:
            memory = model.encode(params, extra)
            extra = None
        logits, caches = model.prefill(params, tokens, extra_embeds=extra,
                                       memory=memory)
        return logits, caches
    return prefill_step


def make_serve_step(model: Model):
    """ONE decode step: next-token logits + greedy token, cache update."""
    def serve_step(params, tokens, caches, memory=None):
        logits, caches = model.decode_step(params, tokens, caches,
                                           memory=memory)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok[:, None], caches
    return serve_step


# ---------------------------------------------------------------------------
# the bundle
# ---------------------------------------------------------------------------

def build(cfg: ModelConfig, shape: ShapeConfig, mesh: Optional[Mesh] = None,
          plan=None, dtype=jnp.bfloat16, opt_cfg: Optional[AdamWConfig] = None,
          scan_layers: Optional[bool] = None,
          moe_impl: Optional[str] = None,
          remat: Optional[bool] = None,
          accum_steps: Optional[int] = None,
          attn_impl: Optional[str] = None,
          ce_chunk: Optional[int] = None,
          policy=None, profile=None, profile_store=None,
          interleave: str = "streams") -> StepBundle:
    if remat is None:
        remat = shape.mode == "train"
    if accum_steps is None:
        accum_steps = default_accum_steps(cfg, shape, mesh)
    if plan is None and policy is not None and mesh is not None:
        # per-shape schedule by policy name: the solver sees this shape's
        # per-device arrived batch (falls back to throughput mode when the
        # batch admits no feasible decomposition)
        from repro.sharding.partition import batch_pspec
        spec = batch_pspec(shape.global_batch, mesh)
        dp = 1
        if spec != P(None):
            entry = spec[0]
            axes = (entry,) if isinstance(entry, str) else (entry or ())
            for a in axes:
                dp *= mesh.shape[a]
        plan = resolve_launch_plan(cfg, mesh, policy, shape.seq_len,
                                   mode=shape.mode,
                                   batch_per_device=shape.global_batch // dp,
                                   profile=profile,
                                   profile_store=profile_store)
    model = make_model(cfg, mesh, plan=plan, scan_layers=scan_layers,
                       moe_impl=moe_impl, remat=remat,
                       interleave=interleave, dtype=dtype)
    if attn_impl is not None:
        model.ctx.attn_impl = attn_impl
    params_abs = abstract_params(model, dtype)
    # FSDP policy: train shards aggressively (opt states dominate);
    # inference keeps weights resident unless truly huge (re-gathering
    # weights every decode step wastes ICI).
    fsdp_threshold = (8 * 1024 * 1024 if shape.mode == "train"
                      else 64 * 1024 * 1024)
    pspecs = params_pspecs(params_abs, cfg, mesh=mesh,
                           fsdp_threshold_elems=fsdp_threshold)
    if mesh is not None:
        params_abs = _with_sharding(params_abs, pspecs, mesh)
        params_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    else:
        params_sh = None
    inputs = input_specs(cfg, shape, mesh, dtype)

    if shape.mode == "train":
        opt_cfg = opt_cfg or AdamWConfig(state_dtype=jnp.bfloat16)
        opt_abs = jax.eval_shape(partial(init_opt_state, cfg=opt_cfg),
                                 params_abs)
        if mesh is not None:
            opt_pspecs = OptState(step=P(),
                                  mu=pspecs, nu=pspecs)
            opt_abs = _with_sharding(opt_abs, opt_pspecs, mesh)
            opt_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                  opt_pspecs)
        else:
            opt_sh = None
        fn = make_train_step(model, opt_cfg, accum_steps=accum_steps,
                             ce_chunk=ce_chunk)
        args = [params_abs, opt_abs, inputs["tokens"]]
        shardings = [params_sh, opt_sh,
                     inputs["tokens"].sharding if mesh else None]
        if "extra" in inputs:
            args.append(inputs["extra"])
            shardings.append(inputs["extra"].sharding if mesh else None)
        out_sh = (params_sh, opt_sh, None) if mesh else None
        return StepBundle(
            name=f"{cfg.name}:{shape.name}:train",
            step_fn=fn, abstract_args=tuple(args),
            in_shardings=tuple(shardings) if mesh else None,
            out_shardings=out_sh,
            model=model, donate_argnums=(0, 1), accum_steps=accum_steps)

    if shape.mode == "prefill":
        fn = make_prefill_step(model)
        args = [params_abs, inputs["tokens"]]
        shardings = [params_sh, inputs["tokens"].sharding if mesh else None]
        if "extra" in inputs:
            args.append(inputs["extra"])
            shardings.append(inputs["extra"].sharding if mesh else None)
        return StepBundle(
            name=f"{cfg.name}:{shape.name}:prefill",
            step_fn=fn, abstract_args=tuple(args),
            in_shardings=tuple(shardings) if mesh else None,
            out_shardings=None, model=model)

    # decode
    caches_abs = decode_cache_specs(model, cfg, shape, mesh, dtype)
    fn = make_serve_step(model)
    args = [params_abs, inputs["tokens"], caches_abs]
    shardings = [params_sh,
                 inputs["tokens"].sharding if mesh else None,
                 jax.tree.map(lambda s: s.sharding, caches_abs)
                 if mesh else None]
    if "memory" in inputs:
        args.append(inputs["memory"])
        shardings.append(inputs["memory"].sharding if mesh else None)
    cache_out_sh = (jax.tree.map(lambda s: s.sharding, caches_abs)
                    if mesh else None)
    return StepBundle(
        name=f"{cfg.name}:{shape.name}:decode",
        step_fn=fn, abstract_args=tuple(args),
        in_shardings=tuple(shardings) if mesh else None,
        out_shardings=(None, cache_out_sh) if mesh else None,
        model=model, donate_argnums=(2,))
