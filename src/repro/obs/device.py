"""On-device executed task spans via fenced eager emission.

The host-lane replay (``obs.replay``) gives the attributor executed
spans without a mesh, but they are *synthetic* — modeled durations run
on worker threads. This module produces spans from the REAL executor:
it runs ``core.dep.moe_apply_dep`` eagerly (outside jit) on a device
mesh under a fence-enabled ``TraceRecorder``. Eager ``shard_map``
executes the walk per-primitive, and with ``fence=True`` the walker
blocks on each task's output (``maybe_fence``) before closing its span,
so every A2E/EXP/E2A/SHARED/GATE span bounds actual device work for
that chunk — the on-device trace ``benchmarks.table7_overlap
--executed`` consumes when a multi-device mesh is available.

Fidelity bound: fencing serializes the dispatch stream at every task
boundary, so cross-lane *overlap* is deliberately sacrificed for
per-task attribution accuracy — the spans order-check the executed
emission and cost-attribute per kind; the overlap claim itself is
gated on the dependency-faithful lane replay (``replay_schedule`` with
``stream_serial_deps``/``stream_major_order`` for the sequential arm).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.obs.trace import Span, TraceRecorder, use_tracer

#: capacity factor generous enough that the proxy layer drops nothing —
#: drops are routing noise the span trace should not depend on
_PROXY_CF = 8.0


def device_mesh(min_devices: int = 2):
    """A ("data", "model") mesh over the local devices, or None when the
    platform cannot host a DEP exchange (fewer than ``min_devices`` or
    an odd device count — the model axis takes 2, data the rest)."""
    n = jax.device_count()
    if n < min_devices or n % 2:
        return None
    from repro.launch.mesh import make_mesh
    return make_mesh((n // 2, 2), ("data", "model"))


@dataclass
class DeviceTrace:
    """Executed spans from one eager fenced DEP layer run."""

    spans: List[Span]          # cat="task" spans in emission order
    out: object                # the layer output (already fenced)
    recorder: TraceRecorder
    wall_s: float


def trace_dep_execution(program, mesh, *, mode: str = "sequence",
                        d_model: int = 32,
                        mcfg: Optional[MoEConfig] = None,
                        dtype=jnp.float32, seed: int = 0) -> DeviceTrace:
    """Run one DEP MoE layer for real on ``mesh`` under ``program`` and
    return the fenced per-task spans.

    The layer is a scaled-down proxy (small d_model, generous capacity):
    the spans' *structure* — emission order, per-kind device cost, one
    span per (stream, chunk) task — is what the attribution consumes,
    and that is fixed by the program, not the layer width. ``mode``
    picks the dispatch path: "sequence" (tokens split over the model
    axis, chunked all_to_all) or "replicated" (decode-style S=1,
    local-expert slices + psum combine).
    """
    from repro.core.dep import moe_apply_dep
    from repro.models.moe import moe_init
    from repro.models.transformer import ExecutionContext

    mo = mesh.shape["model"]
    dp = mesh.size // mo
    E_pad = 2 * mo
    if mcfg is None:
        mcfg = MoEConfig(num_experts=E_pad, top_k=2,
                         expert_ffn_dim=2 * d_model,
                         num_shared_experts=1, shared_ffn_dim=d_model,
                         capacity_factor=_PROXY_CF)
    B = 2 * dp
    S = 4 * mo if mode == "sequence" else 1
    k_p, k_x = jax.random.split(jax.random.PRNGKey(seed))
    params = moe_init(k_p, d_model, mcfg, E_pad)
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    x = jax.random.normal(k_x, (B, S, d_model), dtype)
    ctx = ExecutionContext(mesh=mesh, moe_impl="dep")

    rec = TraceRecorder(fence=True)
    t0 = rec.clock()
    with use_tracer(rec):
        # eager (outside jit): shard_map executes per-primitive, so the
        # walker's fenced spans time real device work per task
        out = moe_apply_dep(params, x, mcfg, ctx, E_pad, plan=program)
    jax.block_until_ready(out)
    wall = rec.clock() - t0
    return DeviceTrace(spans=rec.task_spans(emitted=True), out=out,
                       recorder=rec, wall_s=wall)
