"""Continuous-batching serving engine: a thin loop over the batch/KV
runtime objects.

The engine owns almost nothing anymore — each iteration is

  1. ``BatchScheduler.build_step(waiting, kv)``: reject oversized
     prompts, admit under the configured admission policy (fcfs / spf /
     token_budget), allocate KV slots, group admitted requests by padded
     prefill bucket;
  2. one batched, jitted ``model.prefill`` per ``PrefillGroup`` (chunked
     by the resolved plan's r1·m_a granularity; one compiled program per
     [rows, bucket] shape and static key), scattered into per-slot caches
     by the ``KVCacheManager``;
  3. one ``model.decode_step`` over the full slot batch, with per-slot
     temperature/top-k sampling; finished slots are evicted and their
     requests collected in ``finished``.

``kv_layout="paged"`` swaps the dense per-slot KV rows for the
block-granular ``PagedKVCacheManager`` (``repro.runtime.paging``): slots
hold block tables over a shared page pool, identical prompt prefixes
share pages through a content-hash cache, admission charges only
non-cached pages (watermark hysteresis gates it under pool pressure),
and decode steps grow tail pages on demand — preempting the
cheapest-to-recompute victim back to the waiting queue when the pool
runs dry. ``paging_stats()`` surfaces occupancy / hit-rate / preemption
counters. Decode outputs are bit-identical to the dense layout at equal
kernel blocking (``decode_bc`` = page size).

Scheduling is delegated to a pluggable ``repro.sched.SchedulePolicy``
behind a per-shape ``PlanCache`` — the paper's online phase (Fig. 6):

  * every prefill group resolves a plan for its (bucket, batch) shape
    before the prompt tokens run — a new shape triggers a solve, a
    recurring one hits the cache;
  * every decode step resolves a plan for the KV ledger's
    ``OccupancySummary`` (live slots + context-length histogram), so the
    solver sees the real batch composition instead of the old
    (max_context, live-count) proxy; the plan is re-solved only when the
    composition changes, so steady-state decode pays one dict lookup.

Resolved plans are passed per call into the model (and from there to the
DEP executor) as static arguments; the ``ExecutionContext`` stays an
immutable distribution template with no baked-in schedule.
"""
from __future__ import annotations

import math
import time
import warnings
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.perf_model import HardwareProfile, get_profile
from repro.core.solver import Plan
from repro.models import build_model
from repro.models.transformer import ExecutionContext, Model
from repro.obs.metrics import Counter, MetricsRegistry
from repro.obs.trace import TraceRecorder, use_tracer, watch_compiles
from repro.placement import (ExpertLoadTracker, Placement, SkewSummary,
                             capacity_scale, max_rank_load, rebalance)
from repro.profiling import (DriftMonitor, PeriodicRecalibrator, PlanRefresher,
                             ProfileKey, ProfileStore, StepTimer)
from repro.profiling import calibrate as run_calibration
from repro.runtime.batching import BatchScheduler, PrefillGroup, StepPlan
from repro.runtime.kv import KVCacheManager
from repro.runtime.paging import PagedKVCacheManager
from repro.runtime.request import Request, RequestState
from repro.runtime.sampler import sample
from repro.sched import OccupancySummary, PlanCache, SchedulePolicy


def check_decode_kernel(cfg: ModelConfig, *, mesh=None,
                        max_context: int, kv_layout: str = "dense",
                        decode_bc: Optional[int] = None,
                        dtype=jnp.float32) -> None:
    """Raise ``ValueError`` unless the ragged decode kernel can serve every
    attention layer of ``cfg`` at these cache shapes: full-attention GQA
    caches or MLA latent caches (no ring windows), KV heads that the
    mesh's expert axis divides (each device runs the kernel on its own
    heads; a latent cache is one KV head, so MLA takes one device), and,
    for the dense layout, a context length the kernel can tile."""
    from repro.kernels.decode_attention.kernel import (largest_block_size,
                                                       sublane_tile)
    if not cfg.uses_attention:
        return                  # no layer decodes through a KV cache
    if cfg.attention in ("sliding", "local"):
        raise ValueError(
            "the decode kernel takes full-attention caches; "
            f"{cfg.name} has attention={cfg.attention!r}: pass "
            "attn_impl='xla'")
    mo = mesh.shape.get("model", 1) if mesh is not None else 1
    if cfg.mla_kv_lora_rank and mesh is not None and mesh.size > 1:
        raise ValueError(
            f"{cfg.name}: the latent cache is one KV head and the decode "
            "kernel does not split it over a mesh: pass attn_impl='xla'")
    if cfg.num_kv_heads % mo:
        raise ValueError(
            f"{cfg.num_kv_heads} KV heads do not divide the mesh's "
            f"{mo}-way 'model' axis; the decode kernel runs on each "
            "device's own KV heads: pass attn_impl='xla'")
    if kv_layout == "dense":
        largest_block_size(max_context, decode_bc or 512,
                           sublane_tile(dtype))


@dataclass
class EngineStats:
    prefill_tokens: int = 0
    decode_tokens: int = 0
    steps: int = 0
    # token assignments lost to expert-capacity overflow (counted by
    # moe_dispatch when expert-load telemetry is on; stays 0 otherwise)
    dropped_tokens: int = 0
    # clock starts on first submit/step, NOT at engine construction —
    # construction-time weight init would count as idle serving time
    start_t: Optional[float] = None

    def ensure_started(self) -> None:
        if self.start_t is None:
            self.start_t = time.perf_counter()

    def reset(self) -> None:
        """Zero the counters and re-arm the clock (benchmark warmup)."""
        self.prefill_tokens = 0
        self.decode_tokens = 0
        self.steps = 0
        self.dropped_tokens = 0
        self.start_t = None


class ServingEngine:
    """``plan_policy`` is any repro.sched.SchedulePolicy; ``scheduler`` a
    configured BatchScheduler (or use the ``admission``/``token_budget``
    shorthands). With no policy at all, the engine runs unscheduled
    (dense/capacity MoE or non-MoE models)."""

    def __init__(self, cfg: ModelConfig, params=None, *, num_slots: int = 4,
                 max_context: int = 4096, mesh=None,
                 scheduler: Optional[BatchScheduler] = None,
                 admission: str = "fcfs",
                 token_budget: Optional[int] = None,
                 plan_policy: Optional[SchedulePolicy] = None,
                 telemetry=None,
                 tracer=None,
                 metrics=None,
                 profile=None, calibrate: bool = False,
                 profile_store=None,
                 drift_threshold: Optional[float] = None,
                 drift_min_samples: int = 3,
                 recalibrate_max_age_s: Optional[float] = None,
                 attn_impl: str = "decode_kernel",
                 kv_layout: str = "dense",
                 kv_block_size: int = 32,
                 kv_num_blocks: Optional[int] = None,
                 decode_bc: Optional[int] = None,
                 replicate_hot_k: int = 0,
                 rebalance_threshold: Optional[float] = None,
                 track_expert_load: Optional[bool] = None,
                 interleave: str = "streams",
                 validate: bool = False,
                 dtype=jnp.float32, seed: int = 0):
        self.plan_policy = plan_policy
        self.plan_cache = (PlanCache(plan_policy)
                           if (plan_policy is not None and cfg.is_moe)
                           else None)
        # measured cost models (repro.profiling): an explicit profile= /
        # calibrate= retunes the policy's planner before anything is solved
        self.calibration = None
        self._apply_profile(profile, calibrate, profile_store, mesh)
        # telemetry: StepTimer instance, or False to disable (default on)
        if telemetry is False:
            self.telemetry: Optional[StepTimer] = None
        else:
            self.telemetry = (telemetry if isinstance(telemetry, StepTimer)
                              else StepTimer())
        # tracer: a repro.obs.TraceRecorder (or True for a fresh one);
        # None/False = tracing off — the default, and the compiled
        # programs are bit-identical either way (test-locked)
        if tracer is True:
            tracer = TraceRecorder()
        self.tracer = tracer if isinstance(tracer, TraceRecorder) else None
        # metrics: a repro.obs.MetricsRegistry (shared across engines),
        # None for a fresh private one (default on — sources are only
        # polled at snapshot time), or False to disable
        if metrics is False:
            self.metrics: Optional[MetricsRegistry] = None
        else:
            self.metrics = (metrics if isinstance(metrics, MetricsRegistry)
                            else MetricsRegistry())
        self.drift: Optional[DriftMonitor] = None
        if drift_threshold is not None and self.plan_cache is not None:
            self.drift = DriftMonitor(
                self.plan_cache,
                timer=self.telemetry if self.telemetry is not None
                else StepTimer(),
                threshold=drift_threshold,
                min_samples=drift_min_samples,
                metrics=self.metrics)
        # cron-style background re-calibration: when the stored profile
        # for this host goes stale, re-run the microbenchmarks off the
        # critical path (step() polls; the check is throttled)
        self.recalibrator: Optional[PeriodicRecalibrator] = None
        if (recalibrate_max_age_s is not None
                and self.plan_cache is not None
                and self.profile_store is not None):
            self.recalibrator = PeriodicRecalibrator(
                self.plan_cache, self.profile_store, mesh=mesh,
                max_age_s=recalibrate_max_age_s,
                refresher=self.drift.refresher if self.drift else None,
                timer=self.telemetry, metrics=self.metrics)
        # decode attention defaults to the ragged Pallas kernel: per-slot
        # ledger lengths let it skip KV blocks past each row's context.
        # The choice is settled here: a model or cache shape the kernel
        # cannot take raises now, not at the first decode trace;
        # attn_impl="xla" is the explicit dense path (A/B parity checks)
        if attn_impl == "decode_kernel":
            check_decode_kernel(cfg, mesh=mesh, max_context=max_context,
                                kv_layout=kv_layout, decode_bc=decode_bc,
                                dtype=dtype)
        ctx = ExecutionContext(
            mesh=mesh,
            attn_impl=attn_impl,
            moe_impl="dep" if (mesh is not None and cfg.is_moe)
            else "capacity",
            decode_bc=decode_bc)
        # plans are always resolved (the schedule is observable via
        # resolved_plans()/plan_cache even on one device), but they are only
        # threaded into the compiled programs when the DEP executor can act
        # on them — otherwise every distinct schedule would retrace decode
        # for a program it cannot change
        self._dep_active = ctx.moe_impl == "dep"
        # cross-micro-batch interleaving for the DEP executor: "streams"
        # (default) emits the exec graph's ops in scheduled start order
        # so micro-batch i+1's GATE group is issued before micro-batch
        # i's E2A retires; "off" keeps the sequential per-stream walk.
        # Both execute bit-identical values (parity test-locked).
        if interleave not in ("off", "streams"):
            raise ValueError(f"interleave must be 'off' or 'streams', "
                             f"got {interleave!r}")
        self.interleave = interleave
        # opt-in static verification: every ExecProgram a resolved plan
        # compiles to is run through repro.analysis.graphcheck before it
        # reaches a trace (structure, capacity multiple, deadlock-freedom,
        # hint-vector validity); a tampered/dep-inconsistent hint vector
        # raises AnalysisError at plan time. Programs are hashable, so
        # each distinct program is checked once.
        self.validate = bool(validate)
        self._validated_programs: set = set()
        self.cfg = cfg
        self.model = build_model(cfg, ctx=ctx, dtype=dtype)
        # on a mesh the parameters are placed ONCE: routed experts split
        # over the expert axis (what the DEP executor's shard_map
        # expects), everything else replicated
        self.param_shardings = None
        if mesh is not None:
            from repro.sharding.partition import expert_parallel_shardings
            shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
            self.param_shardings = expert_parallel_shardings(
                shapes, mesh, ctx.expert_axis)
        if params is None:
            params = self._init_params(jax.random.PRNGKey(seed), dtype)
        if self.param_shardings is not None:
            params = jax.device_put(params, self.param_shardings)
        self.params = params
        # expert placement subsystem (observe -> place -> plan): gate loads
        # feed an EWMA tracker; a threshold breach re-solves the expert ->
        # rank map (+ hot replicas) on the refresh worker; the new layout
        # is applied between steps by permuting the stacked expert weights
        self.replicate_hot_k = max(int(replicate_hot_k), 0)
        self.rebalance_threshold = rebalance_threshold
        placement_wanted = (self.replicate_hot_k > 0
                            or rebalance_threshold is not None)
        if track_expert_load is None:
            track_expert_load = placement_wanted
        # stats collection needs the per-layer Python sink (absent under
        # scan_layers) and an MoE model; placement execution needs DEP
        self._track_load = bool(track_expert_load and cfg.is_moe
                                and not self.model.scan_layers)
        if placement_wanted and not self._track_load:
            warnings.warn(
                "replicate_hot_k/rebalance_threshold need expert-load "
                "telemetry (MoE model, scan_layers=False); placement is "
                "disabled", stacklevel=2)
            placement_wanted = False
        self.load_tracker = (ExpertLoadTracker(self.model.E_pad)
                             if self._track_load else None)
        self._ep_ranks = (mesh.shape[ctx.expert_axis]
                          if self._dep_active else 1)
        self.placement: Optional[Placement] = None
        self._pending_placement: Optional[Placement] = None
        self._placement_enabled = placement_wanted and self._dep_active
        if placement_wanted and not self._dep_active:
            warnings.warn(
                "replicate_hot_k/rebalance_threshold act on the DEP "
                "executor (mesh + MoE); load telemetry stays on but no "
                "re-placement will run", stacklevel=2)
        self._placement_refresher: Optional[PlanRefresher] = None
        self._owns_placement_refresher = False
        if self._placement_enabled:
            if self.drift is not None:
                self._placement_refresher = self.drift.refresher
            else:
                self._placement_refresher = PlanRefresher(self.plan_cache)
                self._owns_placement_refresher = True
        self.num_slots = num_slots
        self.max_context = max_context
        self.key = jax.random.PRNGKey(seed + 1)
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"kv_layout={kv_layout!r}; "
                             "choose 'dense' or 'paged'")
        self.kv_layout = kv_layout
        self._paged = kv_layout == "paged"
        if self._paged:
            # paged decode scatters/streams through a block table; ring
            # windows, MLA latent caches and recurrent states have no
            # block-granular layout (ROADMAP follow-up)
            if (cfg.attention != "full" or cfg.mla_kv_lora_rank
                    or cfg.family not in ("dense", "moe")):
                raise ValueError(
                    "kv_layout='paged' requires a full-attention GQA "
                    f"decoder (family={cfg.family!r}, "
                    f"attention={cfg.attention!r}, "
                    f"mla={cfg.mla_kv_lora_rank})")
            self.kv: KVCacheManager = PagedKVCacheManager(
                num_slots, max_context, model=self.model,
                dtype=self.model.dtype, block_size=kv_block_size,
                num_blocks=kv_num_blocks)
        else:
            self.kv = KVCacheManager(num_slots, max_context,
                                     model=self.model,
                                     dtype=self.model.dtype)
        self.scheduler = scheduler if scheduler is not None else \
            BatchScheduler(admission=admission, token_budget=token_budget)
        self.slots: List[Optional[Request]] = [None] * num_slots
        self.last_tokens = jnp.zeros((num_slots, 1), jnp.int32)
        self.temps = jnp.zeros((num_slots,), jnp.float32)
        self.top_ks = jnp.zeros((num_slots,), jnp.int32)
        self.waiting: List[Request] = []
        self.finished: List[Request] = []
        self.stats = EngineStats()
        #: float32 logits [num_slots, V] of the last decode step (rows of
        #: dead slots included), and (slots, logits [n, V] at each row's
        #: padded bucket end) of the last prefill chunk: what parity
        #: checks compare
        self.last_logits = None
        self.last_prefill_logits = None
        # only the executor-visible task graph (keyed by r2/order/m_e) is
        # a static argument: plans differing in modeled throughput share
        # one compiled program, so retraces are bounded by distinct
        # executable schedules. The KV caches are donated: each step
        # writes its new rows into the cache buffers in place, where an
        # undonated cache costs a whole-cache copy a layer
        self._decode_jit = jax.jit(
            self._decode_step,
            static_argnames=("plan", "use_topk", "placement",
                             "cap_scale", "collect_stats"),
            donate_argnames=("caches",))
        # the prefill likewise: one program per [rows, bucket] token shape
        # and static key, with the weights a traced argument (never baked
        # into the program as constants)
        self._prefill_jit = jax.jit(
            self._prefill,
            static_argnames=("plan", "placement", "cap_scale",
                             "collect_stats", "seq_budget"))
        self._memory = None
        self._h_ttft = self._h_tpot = None
        self._h_decode = self._h_prefill = None
        self._c_rows: Dict[Tuple[str, str], Counter] = {}
        self._c_prefill_traces: Optional[Counter] = None
        self._c_kv_tokens: Optional[Counter] = None
        if self.metrics is not None:
            self._register_metrics(self.metrics)

    def _init_params(self, key, dtype):
        """The engine's own weights, held in the serving dtype. Float32
        engines keep the eager initializer, so their weights stay
        bit-identical to ``Model.init`` (tests compare against it); any
        other dtype initializes and casts layer by layer under jit, so the
        float32 intermediates never all live on the device at once, and
        lands each array on its placement directly."""
        if jnp.dtype(dtype) == jnp.float32:
            return self.model.init(key)
        return self.model.init(key, dtype=dtype,
                               shardings=self.param_shardings)

    # ------------------------------------------------------------------
    # observability (repro.obs): metrics registration, phase spans
    # ------------------------------------------------------------------
    def _register_metrics(self, m: MetricsRegistry) -> None:
        """Wire every stat surface the engine owns into one registry:
        latency histograms observed at event sites, the existing counter
        surfaces as polled snapshot sources, and one registry-level
        ``reset()`` that clears ALL of them (including the StepTimer /
        expert-load EWMAs the old per-surface resets leaked)."""
        self._h_ttft = m.histogram(
            "repro_engine_ttft_seconds", "time to first token")
        self._h_tpot = m.histogram(
            "repro_engine_tpot_seconds", "mean time per output token")
        self._h_decode = m.histogram(
            "repro_engine_decode_step_seconds", "decode step wall time")
        self._h_prefill = m.histogram(
            "repro_engine_prefill_chunk_seconds",
            "prefill chunk wall time")
        # expert rows per phase: what the MoE buffers hold (computed) and
        # what the router sent (routed); their ratio is the padded share
        for kind, what in (("computed", "rows the routed-expert FFN "
                            "computes, padding included"),
                           ("routed", "token-expert assignments routed")):
            for phase in ("prefill", "decode"):
                self._c_rows[kind, phase] = m.counter(
                    f"repro_engine_expert_rows_{kind}_total", what,
                    labels={"phase": phase})
        self._c_prefill_traces = m.counter(
            "repro_engine_prefill_traces_total",
            "prefill programs traced: distinct token shapes and static keys")
        self._c_kv_tokens = m.counter(
            "repro_engine_decode_kv_tokens_total",
            "context positions attended, summed over the decode steps' "
            "live rows")
        m.gauge("repro_engine_kv_cache_bytes",
                "bytes the engine's KV caches hold on the device (0 until "
                "they are made)", fn=self._kv_cache_bytes)
        m.register_source("repro_engine", self._engine_snapshot)
        m.register_reset(self.stats.reset)
        if self.plan_cache is not None:
            m.register_source("repro_plan_cache",
                              self.plan_cache.stats.as_dict)
        if self.telemetry is not None:
            m.register_source("repro_telemetry", self.telemetry.snapshot)
            m.register_reset(self.telemetry.reset)
        if self.load_tracker is not None:
            m.register_source("repro_expert_load",
                              self.load_tracker.snapshot)
            m.register_reset(self.load_tracker.reset)
        if self._paged:
            m.register_source("repro_paging", self.kv.paging_summary)
            m.register_reset(self.kv.paging.reset)
        if self.drift is not None:
            m.register_source("repro_drift", self._drift_snapshot)

    def _engine_snapshot(self) -> Dict[str, float]:
        return {"prefill_tokens_total": float(self.stats.prefill_tokens),
                "decode_tokens_total": float(self.stats.decode_tokens),
                "steps_total": float(self.stats.steps),
                "dropped_tokens_total": float(self.stats.dropped_tokens),
                "waiting": float(len(self.waiting)),
                "live_slots": float(sum(r is not None
                                        for r in self.slots))}

    def _kv_cache_bytes(self) -> float:
        caches = self.kv.caches
        return float(sum(a.nbytes for a in jax.tree.leaves(caches))
                     if caches is not None else 0)

    def _drift_snapshot(self) -> Dict[str, float]:
        st = self.drift.stats
        return {"observations_total": float(st.observations),
                "events_total": float(st.drift_events)}

    def reset_stats(self) -> None:
        """THE warmup boundary: one call clears every stat surface. With
        a metrics registry this routes through ``MetricsRegistry.reset()``
        (counters, histograms, and the registered reset hooks); without
        one it clears the same surfaces directly. Either way the
        StepTimer EWMAs and expert-load EWMAs restart — the old
        ``stats.reset()``-only idiom left them carrying warmup samples."""
        if self.metrics is not None:
            self.metrics.reset()
        else:
            self.stats.reset()
            if self.telemetry is not None:
                self.telemetry.reset()
            if self.load_tracker is not None:
                self.load_tracker.reset()
            if self._paged:
                self.kv.paging.reset()
        if self.tracer is not None:
            self.tracer.clear()

    @property
    def tracer(self) -> Optional[TraceRecorder]:
        return self._tracer

    @tracer.setter
    def tracer(self, tracer: Optional[TraceRecorder]) -> None:
        """Set the engine's recorder, or unset it with None. A set
        recorder also records garbage collections, and compiles made
        while it is scoped; unsetting it stops both."""
        old = getattr(self, "_tracer", None)
        if old is not None:
            old.unwatch_gc()
        self._tracer = tracer
        if tracer is not None and tracer.enabled:
            tracer.watch_gc()
            watch_compiles()

    def _count_rows(self, phase: str, computed: int, routed: int) -> None:
        """Add one MoE call's expert rows to the phase's counters."""
        if self._c_rows:
            self._c_rows["computed", phase].inc(computed)
            self._c_rows["routed", phase].inc(routed)

    @contextmanager
    def _phase(self, name: str, **args):
        """Phase span + active-tracer scope around a step phase. With no
        tracer this adds NOTHING to the path (no contextvar touch), so
        the executor walk and the compiled programs are unchanged."""
        if self.tracer is None:
            yield
            return
        with use_tracer(self.tracer), \
                self.tracer.span(name, track="engine", **args):
            yield

    # ------------------------------------------------------------------
    # measured cost models
    # ------------------------------------------------------------------
    def _apply_profile(self, profile, calibrate: bool, profile_store,
                       mesh) -> None:
        """Retune the policy's planner onto a measured HardwareProfile.

        ``calibrate=True`` runs the on-device microbenchmarks now (fast
        sweep) and, when a ``profile_store`` is given, persists the fit so
        the next process can pass ``profile=<name>`` instead of
        re-measuring. ``profile=`` accepts a HardwareProfile, a stored
        profile name, or a registry name (repro.core.perf_model.PROFILES).
        """
        store = None
        if profile_store is not None:
            store = (profile_store if isinstance(profile_store, ProfileStore)
                     else ProfileStore(profile_store))
        self.profile_store = store
        if not calibrate and profile is None:
            return
        if calibrate:
            key = ProfileKey.for_host(mesh)
            name = profile if isinstance(profile, str) else key.slug()
            result = run_calibration(name=name, fast=True, mesh=mesh)
            hw = result.profile
            self.calibration = result
            if store is not None:
                store.put_calibration(result, key, name=name)
        elif isinstance(profile, HardwareProfile):
            hw = profile
        else:
            try:
                hw = (store.load_profile(profile) if store is not None
                      else get_profile(profile))
            except KeyError:
                hw = get_profile(profile)
        reprofile = getattr(self.plan_policy, "reprofile", None)
        if callable(reprofile):
            reprofile(hw)
        elif self.plan_policy is not None:
            warnings.warn(
                f"policy {getattr(self.plan_policy, 'name', '?')!r} has no "
                "reprofile() hook; profile=/calibrate= had no effect on "
                "planning", stacklevel=3)

    def _observe(self, phase: str, key, measured_s: float,
                 plan: Optional[Plan], predicted_scale: float = 1.0) -> None:
        if phase == "decode" and self._h_decode is not None:
            self._h_decode.observe(measured_s)
        elif phase == "prefill" and self._h_prefill is not None:
            self._h_prefill.observe(measured_s)
        predicted = None
        breakdown = None
        if plan is not None and plan.makespan > 0.0:
            predicted = plan.makespan * predicted_scale
            # the lowered graph's per-primitive split of that prediction —
            # lets drift attribution retune gemm/attn/comm separately
            if plan.breakdown is not None:
                breakdown = plan.breakdown.scaled(predicted_scale).as_dict()
        if self.drift is not None:
            self.drift.observe(key, measured_s, predicted, phase=phase,
                               breakdown=breakdown)
        elif self.telemetry is not None:
            self.telemetry.observe(phase, measured_s, predicted_s=predicted,
                                   key=key, breakdown=breakdown)

    def close(self) -> None:
        """Stop the background refresh/recalibration workers (if any)
        and the tracer's collection hook."""
        if self._tracer is not None:
            self._tracer.unwatch_gc()
        if self.drift is not None:
            self.drift.close()
        if self.recalibrator is not None:
            self.recalibrator.close()
        if self._owns_placement_refresher \
                and self._placement_refresher is not None:
            self._placement_refresher.close()

    # ------------------------------------------------------------------
    # scheduling
    # ------------------------------------------------------------------
    def _resolve_plan(self, phase: str, seq_bucket: Optional[int] = None,
                      batch_per_device: Optional[int] = None,
                      occupancy: Optional[OccupancySummary] = None,
                      skew: Optional[SkewSummary] = None
                      ) -> Optional[Plan]:
        if self.plan_cache is None:
            return None
        return self.plan_cache.get(phase, seq_bucket, batch_per_device,
                                   occupancy=occupancy, skew=skew)

    def _exec_program(self, plan: Optional[Plan],
                      streams: Optional[int] = None):
        """The ``ExecProgram`` the DEP executor walks for ``plan`` —
        hashable, keyed by (r1, r2, order, m_e, interleave, hints) plus
        the active placement's replica count and epoch, so plans that
        compile to the same program share one trace and a re-balance
        keys a fresh one. ``streams`` overrides the stream split (the
        prefill path passes the lowered chunk's micro-batch count — the
        r1 streams one prefill call covers); decode uses the plan's
        r1."""
        if plan is None or (not self._dep_active and not self.validate):
            return None
        hot, epoch = 0, 0
        if self.placement is not None:
            hot, epoch = self.placement.hot_experts, self.placement.epoch
        program = plan.exec_program(streams=streams, hot_experts=hot,
                                    placement_epoch=epoch,
                                    interleave=self.interleave)
        if self.validate:
            self._check_program(program)
        # single-device engines still resolve plans (observable via
        # resolved_plans()), but the compiled programs must not see them
        return program if self._dep_active else None

    def _check_program(self, program) -> None:
        """Static-verify an ExecProgram (see ``validate``); memoized on
        the program's own hash so each distinct program pays once."""
        if program in self._validated_programs:
            return
        from repro.analysis import AnalysisError
        from repro.analysis.graphcheck import check_exec_program
        violations = check_exec_program(program)
        if violations:
            raise AnalysisError(violations)
        self._validated_programs.add(program)

    # ------------------------------------------------------------------
    # expert placement (observe -> place -> plan)
    # ------------------------------------------------------------------
    _PLACEMENT_KEY = ("__placement__",)
    # tracked routing steps before the first re-balance may be solved
    _REBALANCE_MIN_OBSERVATIONS = 3
    # cap on the skew-driven capacity multiplier (bounds buffer growth)
    _MAX_CAPACITY_SCALE = 4.0

    def _current_skew(self) -> Optional[SkewSummary]:
        """The quantized skew fingerprint plans are resolved under; None
        when telemetry is off or routing is (still) uniform — the legacy
        key space and cost model."""
        if self.load_tracker is None:
            return None
        s = self.load_tracker.summary(placement=self.placement,
                                      num_ranks=self._ep_ranks)
        return None if s.is_uniform else s

    def _capacity_scale(self, skew: Optional[SkewSummary]) -> float:
        """Static capacity multiplier for the executed dispatch, rounded
        up to a power of two (bounds trace cardinality) and capped at
        ``_MAX_CAPACITY_SCALE`` (bounds buffer growth)."""
        if skew is None or not self._dep_active:
            return 1.0
        raw = capacity_scale(skew, self.cfg.moe.capacity_factor)
        if raw <= 1.0:
            return 1.0
        return float(min(2.0 ** math.ceil(math.log2(raw)),
                         self._MAX_CAPACITY_SCALE))

    def rank_imbalance(self) -> float:
        """Worst EP rank's cold (non-replicated) load as a multiple of
        the uniform 1/eg share under the ACTIVE placement — the
        re-balance trigger metric (1.0 = perfectly flat)."""
        if self.load_tracker is None:
            return 1.0
        pl = self.placement if self.placement is not None else \
            Placement.uniform(self.model.E_pad, self._ep_ranks)
        return max_rank_load(pl, self.load_tracker.aggregate()) \
            * self._ep_ranks

    def _solve_placement(self) -> None:
        """Refresh-worker job: greedy re-placement against the tracked
        loads; the result is STAGED — ``step()`` applies it between
        decode steps (weight permutation must not race a running step)."""
        epoch = (self.placement.epoch if self.placement else 0) + 1
        self._pending_placement = rebalance(
            self.load_tracker.aggregate(), self._ep_ranks,
            replicate_hot_k=self.replicate_hot_k, epoch=epoch)

    def _maybe_rebalance(self) -> bool:
        """Schedule a background re-placement when the active layout's
        rank imbalance breaches ``rebalance_threshold``. Mirrors the
        drift machinery: one in-flight episode, never blocks a step."""
        if (not self._placement_enabled
                or self.rebalance_threshold is None
                or self._pending_placement is not None):
            return False
        if (self.load_tracker.observations
                < self._REBALANCE_MIN_OBSERVATIONS):
            return False
        if self.rank_imbalance() <= self.rebalance_threshold:
            return False
        return self._placement_refresher.request_job(
            self._PLACEMENT_KEY, self._solve_placement)

    def rebalance_now(self) -> Optional[Placement]:
        """Synchronous re-placement (tests / maintenance windows): solve
        against the tracked loads and apply immediately."""
        if not self._placement_enabled or self.load_tracker is None:
            return None
        self._solve_placement()
        pending, self._pending_placement = self._pending_placement, None
        self._apply_placement(pending)
        return self.placement

    def _apply_placement(self, new: Placement) -> None:
        """Install a re-balanced layout: permute the stacked expert
        weights so physical slot ``new.perm[e]`` holds logical expert
        ``e``, bump the active placement (epoch keys fresh exec graphs
        and plan-cache entries), and invalidate stale-epoch entries."""
        old = self.placement if self.placement is not None else \
            Placement.uniform(new.num_experts, new.num_ranks)
        # physical gather realizing the old -> new layout change:
        # new_phys[p] = logical[inv_new[p]] = old_phys[old.perm[inv_new[p]]]
        inv_new = np.argsort(np.asarray(new.perm))
        gather = np.asarray(old.perm)[inv_new]
        if not np.array_equal(gather, np.arange(new.num_experts)):
            idx = jnp.asarray(gather)
            for i, layer in enumerate(self.params["layers"]):
                if "moe" in layer and "experts" in layer["moe"]:
                    experts = jax.tree.map(lambda a: a[idx],
                                           layer["moe"]["experts"])
                    if self.param_shardings is not None:
                        experts = jax.device_put(
                            experts, self.param_shardings["layers"][i]
                            ["moe"]["experts"])
                    layer["moe"]["experts"] = experts
        self.placement = new
        if self.metrics is not None:
            self.metrics.counter(
                "repro_rebalance_applied_total",
                "expert re-placements installed between steps").inc()
        if self.plan_cache is not None:
            # entries solved under an older placement epoch can never be
            # served again (lookups now carry the new epoch's summary)
            for key in list(self.plan_cache.entries()):
                tail = key[-1]
                if isinstance(tail, SkewSummary) and tail.epoch != new.epoch:
                    self.plan_cache.invalidate(key)

    def expert_load(self) -> Optional[Dict[str, float]]:
        """Expert-load telemetry snapshot (None when tracking is off)."""
        if self.load_tracker is None:
            return None
        return dict(observations=float(self.load_tracker.observations),
                    imbalance=self.load_tracker.imbalance(),
                    rank_imbalance=self.rank_imbalance(),
                    dropped_tokens=float(self.stats.dropped_tokens),
                    epoch=float(self.placement.epoch
                                if self.placement else 0),
                    hot_experts=float(self.placement.hot_experts
                                      if self.placement else 0))

    def resolved_plans(self) -> Dict[Any, Plan]:
        """Every resolution so far: prefill plans keyed
        (phase, bucket, batch), decode plans keyed
        (phase, OccupancySummary)."""
        return self.plan_cache.entries() if self.plan_cache else {}

    # ------------------------------------------------------------------
    @property
    def caches(self):
        return self.kv.caches

    def submit(self, req: Request):
        self.stats.ensure_started()
        self.waiting.append(req)

    def _finish(self, req: Request, state: RequestState,
                now: float) -> None:
        """THE single request-termination site (finished / length-capped
        / rejected): stamps the terminal state, records the lifecycle
        spans and the TTFT/TPOT observations."""
        req.state = state
        req.finish_t = now
        self.finished.append(req)
        if self.tracer is not None:
            self.tracer.request_lifecycle(req, finish_t=now)
        if self.metrics is not None:
            self.metrics.counter(
                "repro_engine_requests_total",
                "requests by terminal state",
                labels={"state": state.value}).inc()
            if req.ttft is not None:
                self._h_ttft.observe(req.ttft)
            if req.tpot is not None:
                self._h_tpot.observe(req.tpot)

    def _prefill_group(self, group: PrefillGroup):
        """Run one same-bucket group as batched prefill calls, chunked by
        the resolved plan's r1·m_a granularity (the AG-side samples one
        plan iteration admits), and scatter the rows into per-slot
        caches."""
        self.kv.ensure_caches()
        if group.bucket == 0:
            # empty/single-token prompts: nothing to prefill, the (only)
            # prompt token is fed through the shared decode step
            with self._phase("kv_merge"):
                for slot, req in zip(group.slots, group.requests):
                    self.kv.reset_slot(slot)
                    self._activate(slot, req, prefilled=0)
            return
        with self._phase("plan"):
            skew = self._current_skew()
            plan = self._resolve_plan("prefill", group.bucket,
                                      len(group.requests), skew=skew)
            plan_key = ("prefill", group.bucket, len(group.requests))
            if skew is not None:
                plan_key = plan_key + (skew,)
            chunk = len(group.requests)
            n_mb = 1
            if plan is not None:
                # chunk granularity comes from the lowered task graph —
                # the number of AG micro-batches one plan iteration
                # admits, times the per-micro-batch sample count — rather
                # than re-deriving plan.r1 * plan.m_a by hand (one
                # Plan->structure translation). The SAME n_mb is the
                # stream split of the interleaved prefill program below:
                # one prefill call covers the n_mb micro-batch streams the
                # solver scheduled, and the MoE walk interleaves them
                # instead of the host loop running them back-to-back.
                from repro.core.taskgraph import ATTN, LoweringSpec, lower
                graph = lower(plan, LoweringSpec(T=1))
                n_mb = len(graph.tasks_of(ATTN, layer=0))
                chunk = max(min(n_mb * max(int(plan.m_a), 1), chunk), 1)
            cap_scale = self._capacity_scale(skew)
        for ofs in range(0, len(group.requests), chunk):
            reqs = group.requests[ofs:ofs + chunk]
            slots = group.slots[ofs:ofs + chunk]
            toks = np.zeros((len(reqs), group.bucket), np.int32)
            lengths = []
            token_rows = []
            for j, req in enumerate(reqs):
                feed = req.resume_tokens     # prompt (+ preempted output)
                Lp = len(feed) - 1
                toks[j, :Lp] = feed[:Lp]
                lengths.append(Lp)
                token_rows.append(feed[:Lp])
            t0 = time.perf_counter()
            with self._phase("prefill_chunk", bucket=group.bucket,
                             reqs=len(reqs)):
                with self._phase("prefill_dispatch"):
                    program = self._exec_program(plan, streams=n_mb)
                    logits, prefilled, mstats = self._prefill_jit(
                        self.params, toks, plan=program,
                        placement=self.placement
                        if self._dep_active else None,
                        cap_scale=cap_scale,
                        collect_stats=self._track_load,
                        seq_budget=self.max_context)
                with self._phase("prefill_wait"):
                    jax.block_until_ready(prefilled)
            self._count_rows(
                "prefill",
                self.model.expert_rows(len(reqs), group.bucket, program,
                                       cap_scale),
                sum(lengths) * self.model.routed_per_token)
            self.last_prefill_logits = (list(slots), logits)
            if mstats is not None:
                self.load_tracker.observe(np.asarray(mstats.load))
                self.stats.dropped_tokens += int(mstats.dropped)
            # plan.makespan models one full r1·m_a chunk; pro-rate the
            # prediction for a remainder chunk so it isn't biased short
            self._observe("prefill", plan_key, time.perf_counter() - t0,
                          plan, predicted_scale=len(reqs) / chunk)
            with self._phase("kv_merge"):
                if self._paged:
                    # token ids key the prefix cache: shared full blocks
                    # map to already-resident pages and skip the copy
                    self.kv.merge_prefill(slots, prefilled, lengths,
                                          tokens=token_rows)
                else:
                    self.kv.merge_prefill(slots, prefilled, lengths)
                for slot, req, Lp in zip(slots, reqs, lengths):
                    self._activate(slot, req, prefilled=Lp)

    def _activate(self, slot: int, req: Request, prefilled: int):
        self.stats.ensure_started()
        if req.admit_t is None:          # first admission, not a resume
            req.admit_t = time.perf_counter()
        feed = req.resume_tokens
        self.last_tokens = self.last_tokens.at[slot, 0].set(
            feed[-1] if feed else 0)
        self.temps = self.temps.at[slot].set(req.temperature)
        self.top_ks = self.top_ks.at[slot].set(req.top_k)
        self.stats.prefill_tokens += prefilled
        req.state = RequestState.RUNNING
        self.slots[slot] = req

    def _prefill_one(self, slot: int, req: Request):
        """Single-request shim over the batched path (kept for parity
        tests and direct callers): prefill the first L-1 prompt tokens
        into ``slot``; the last prompt token is fed through the shared
        decode step."""
        if len(req.resume_tokens) > self.max_context:
            raise ValueError(
                f"prompt of {len(req.resume_tokens)} tokens exceeds "
                f"max_context={self.max_context}; submit() rejects such "
                "requests instead of truncating")
        self.kv.take(slot)
        Lp = max(len(req.resume_tokens) - 1, 0)
        if Lp == 0:
            bucket = 0
        elif self.cfg.family in ("ssm", "hybrid"):
            bucket = Lp
        else:
            from repro.sched import bucket_length
            bucket = min(bucket_length(Lp), self.max_context)
        self._prefill_group(PrefillGroup(bucket, [slot], [req]))

    def _admit(self) -> StepPlan:
        with self._phase("schedule"):
            step_plan = self.scheduler.build_step(
                self.waiting, self.kv, max_context=self.max_context,
                exact_length=self.cfg.family in ("ssm", "hybrid"))
        now = time.perf_counter()
        for req in step_plan.rejected:
            self._finish(req, RequestState.REJECTED, now)
        for group in step_plan.prefills:
            self._prefill_group(group)
        return step_plan

    # ------------------------------------------------------------------
    def _prefill(self, params, tokens, last_positions=None, plan=None,
                 placement=None, cap_scale=1.0, collect_stats=False,
                 seq_budget=None):
        """The body of ``_prefill_jit``: ``model.prefill`` of ``tokens``
        [rows, bucket] into fresh caches of ``seq_budget`` rows. Returns
        (float32 logits [rows, V] at each row's ``last_positions``, or at
        the bucket end; the caches; the stacked ``MoEStats``, or None
        unless ``collect_stats``)."""
        # Python runs this body only while JAX traces a new program
        if self._c_prefill_traces is not None:
            self._c_prefill_traces.inc()
        out = self.model.prefill(
            params, tokens, seq_budget=seq_budget, plan=plan,
            last_positions=last_positions, placement=placement,
            return_moe_stats=collect_stats, capacity_scale=cap_scale)
        return out[0][:, 0], out[1], out[2] if collect_stats else None

    def _decode_step(self, params, tokens, caches, temps, top_ks, key,
                     lengths, block_tables=None, plan=None, use_topk=False,
                     placement=None, cap_scale=1.0, collect_stats=False):
        # placement / cap_scale / collect_stats are static: with the
        # defaults the model compiles the exact legacy program (no stats
        # reductions, uniform dispatch), so engines without expert-load
        # telemetry trace nothing new
        if collect_stats:
            logits, caches, mstats = self.model.decode_step(
                params, tokens, caches, plan=plan, lengths=lengths,
                block_tables=block_tables, placement=placement,
                return_moe_stats=True, capacity_scale=cap_scale)
        else:
            logits, caches = self.model.decode_step(
                params, tokens, caches, plan=plan, lengths=lengths,
                block_tables=block_tables, placement=placement,
                capacity_scale=cap_scale)
            mstats = None
        # use_topk is static: when no live request truncates, the compiled
        # program skips the per-slot [B, V] threshold sort entirely
        with jax.named_scope("sampler"):
            nxt = sample(key, logits[:, -1], temps,
                         top_ks if use_topk else 0)
        return nxt[:, None], caches, mstats, logits[:, -1]

    # ------------------------------------------------------------------
    # paged-KV capacity management
    # ------------------------------------------------------------------
    def _ensure_decode_capacity(self, live: List[int]) -> List[int]:
        """Grow each live slot's tail KV page before the decode write.
        On pool exhaustion, preempt the victim with the cheapest
        recompute (fewest accumulated tokens; youngest arrival breaks
        ties) — its pages are freed and the request re-queued at the HEAD
        of waiting for re-prefill from ``resume_tokens``. When no other
        slot is left to evict, the needy request ends LENGTH_CAPPED (the
        'keep' branch: recompute-later loses to keeping the rest of the
        batch running). Returns the slots that can decode this step."""
        ready: List[int] = []
        pending = list(live)
        while pending:
            i = pending.pop(0)
            ok = True
            while not self.kv.ensure_decode_page(i):
                candidates = [s for s in ready + pending if s != i]
                if not candidates:
                    req = self.slots[i]
                    self._finish(req, RequestState.LENGTH_CAPPED,
                                 time.perf_counter())
                    self.slots[i] = None
                    self.kv.free(i)
                    ok = False
                    break
                victim = min(candidates,
                             key=lambda s: (self.kv.length(s),
                                            -self.slots[s].arrival_t))
                self._preempt(victim)
                if victim in ready:
                    ready.remove(victim)
                if victim in pending:
                    pending.remove(victim)
            if ok:
                ready.append(i)
        return ready

    def _preempt(self, slot: int) -> None:
        """Evict a running request to recompute: free its pages (shared
        prefix pages stay cached) and re-queue it at the head of the
        waiting line so it re-prefills — prompt AND generated tokens —
        as soon as the pool allows."""
        req = self.slots[slot]
        req.state = RequestState.WAITING
        req.preemptions += 1
        self.kv.paging.preemptions += 1
        self.slots[slot] = None
        self.kv.free(slot)
        self.last_tokens = self.last_tokens.at[slot, 0].set(0)
        self.waiting.insert(0, req)

    def paging_stats(self) -> Optional[Dict[str, float]]:
        """Block occupancy / prefix hit-rate / preemption counters
        (None under the dense layout)."""
        return self.kv.paging_summary() if self._paged else None

    def step(self) -> bool:
        """One engine iteration; returns False when idle."""
        if self.recalibrator is not None:
            # throttled staleness check; calibration runs on the worker
            self.recalibrator.maybe_recalibrate()
        with self._phase("admit"):
            self._admit()
        live = [i for i, r in enumerate(self.slots) if r is not None]
        if not live:
            return False
        if self._paged:
            # every live slot needs a page for this step's KV write;
            # exhaustion preempts the cheapest-to-recompute victim
            live = self._ensure_decode_capacity(live)
            if not live:
                # capacity actions (preempt/cap) happened; not idle
                return True
        self.stats.ensure_started()
        # a re-balance staged by the refresh worker lands between steps:
        # the weight permutation + epoch bump must not race a running
        # decode, and the epoch keys fresh exec graphs from here on
        if self._pending_placement is not None:
            pending, self._pending_placement = self._pending_placement, None
            self._apply_placement(pending)
        with self._phase("plan"):
            # decode plan solved on the ledger's real composition (live
            # slots + context-length histogram) AND the observed
            # routing-skew fingerprint; re-resolves only when either
            # changes
            occ = self.kv.occupancy()
            skew = self._current_skew()
            plan = self._resolve_plan("decode", occupancy=occ, skew=skew)
            plan_key = (("decode", occ) if skew is None
                        else ("decode", occ, skew))
            program = self._exec_program(plan)
            cap_scale = self._capacity_scale(skew)
            self.key, sub = jax.random.split(self.key)
            use_topk = any(r is not None and r.top_k > 0
                           for r in self.slots)
            # the ledger's per-slot context lengths drive the attention
            # mask AND the ragged kernel's block skip (dead slots decode
            # as len 0)
            host_lengths = self.kv.lengths()
            lengths = jnp.asarray(host_lengths, jnp.int32)
            tables = self.kv.table_array() if self._paged else None
        t0 = time.perf_counter()
        with self._phase("decode_step", step=self.stats.steps,
                         live=len(live)):
            with self._phase("decode_dispatch"):
                nxt, new_caches, mstats, self.last_logits = \
                    self._decode_jit(
                        self.params, self.last_tokens, self.kv.caches,
                        self.temps, self.top_ks, sub, lengths, tables,
                        plan=program, use_topk=use_topk,
                        placement=self.placement
                        if self._dep_active else None,
                        cap_scale=cap_scale,
                        collect_stats=self._track_load)
                # the step consumed the donated caches: install its own
                # at once, so nothing holds the deleted buffers
                self.kv.caches = new_caches
            with self._phase("decode_wait"):
                jax.block_until_ready(nxt)
        measured = time.perf_counter() - t0
        self._count_rows(
            "decode",
            self.model.expert_rows(self.num_slots, 1, program, cap_scale),
            len(live) * self.model.routed_per_token)
        if self._c_kv_tokens is not None:
            self._c_kv_tokens.inc(sum(host_lengths[i] for i in live))
        with self._phase("emit"):
            self._emit(live, nxt, mstats, plan_key, plan, measured)
        self.stats.steps += 1
        return True

    def _emit(self, live: List[int], nxt, mstats, plan_key,
              plan: Optional[Plan], measured: float) -> None:
        """After a decode step: observe its time, install its tokens, read
        them back and hand each live request its own, finishing those that
        are done."""
        # measured decode wall-time vs the plan's modeled makespan: this is
        # the observe edge of the profiling loop — a sustained residual
        # breach re-solves THIS occupancy's plan on the refresh worker, so
        # the step itself never waits on Algorithm 1
        self._observe("decode", plan_key, measured, plan)
        if mstats is not None:
            # the observe edge of the PLACEMENT loop: gate loads feed the
            # EWMA tracker, capacity-overflow drops surface in the stats,
            # and a rank-imbalance breach stages a background re-placement
            self.load_tracker.observe(np.asarray(mstats.load))
            self.stats.dropped_tokens += int(mstats.dropped)
            self._maybe_rebalance()
        self.last_tokens = nxt
        self.kv.note_decode(live)
        toks = np.asarray(nxt[:, 0])
        now = time.perf_counter()
        for i in live:
            req = self.slots[i]
            req.output.append(int(toks[i]))
            if req.first_token_t is None:
                req.first_token_t = now
            self.stats.decode_tokens += 1
            # ledger length > max_context: the cache is full (this step
            # attended all C rows and wrote the last one); another decode
            # would clamp its write to C-1 and clobber that row, so the
            # request terminates at the cap instead of corrupting KV
            capped = self.kv.length(i) > self.max_context
            if req.done or capped:
                self._finish(req, RequestState.FINISHED if req.done
                             else RequestState.LENGTH_CAPPED, now)
                self.slots[i] = None
                self.kv.free(i)

    def run(self, max_steps: int = 10_000) -> List[Request]:
        """Drive the engine until idle (or ``max_steps``); returns the
        requests that finished during this call."""
        start = len(self.finished)
        for _ in range(max_steps):
            if not self.step() and not self.waiting:
                break
        return self.finished[start:]
