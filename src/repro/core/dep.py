"""DEP execution on a TPU mesh: the paper's A2E/E2A as r2-chunked
all_to_all collectives inside shard_map.

Adaptation (DESIGN.md §2): AG/EG are roles of mesh axes, not disjoint
device groups. Attention runs data-parallel over ("pod","data") and
tensor-parallel over "model"; routed experts are expert-parallel over
"model". The two DEP communication phases map to:

  A2E  = all_to_all(buffers, "model", split=expert_dim, concat=capacity)
  E2A  = all_to_all(outputs, "model", split=capacity,  concat=expert_dim)

FinDEP's fine-grained r2 chunking splits the capacity dimension into r2
chunks and emits chunk k+1's A2E before chunk k's expert FFN retires, so
XLA's async collective scheduler can overlap transport with expert compute
— the TPU analogue of the paper's multi-stream schedule.

The executor is a WALKER over the task-graph IR: ``moe_apply_dep`` lowers
the resolved plan to a ``taskgraph.ExecProgram`` (or takes one directly)
and emits one jax op group per task of ``program.walk()`` — GATE →
router dispatch, A2E/E2A → chunk all_to_all (or buffer slice / psum
combine in replicated decode mode), EXP → routed-expert FFN, SHARED →
shared-expert GEMM segment. The solved task order (ASAS/AASS) is encoded
in the graph's SHARED boundary indices, so the executed order always
matches what the simulator scheduled — one lowering, not three
hand-rolled interpretations.

Cross-micro-batch interleaving: an ``ExecProgram`` lowered with r1 > 1
covers r1 micro-batch STREAMS of the same layer. Streams are a capacity
split of one router dispatch (token→expert assignment and drops are
stream-count invariant), so under ``interleave="streams"`` the walk
emits all streams' ops in the graph's SCHEDULED start order — stream
i+1's GATE-group work is issued before stream i's E2A retires, the
collective-matmul idiom — while ``interleave="off"`` runs the streams
back-to-back (the historical sequential walk). Both emit bit-identical
values; only the achieved comm/compute overlap differs.

Two dispatch modes:
  * "sequence" (train / prefill): local tokens are split over the "model"
    axis (sequence dim), each peer routes its slice, buffers exchanged
    with all_to_all. This is the paper's dispatch/combine, collective-for-
    collective.
  * "replicated" (decode): tokens are replicated over "model" (batch/seq
    too small to split); each peer computes only its local experts'
    outputs and the combine is a single psum — no dispatch collective.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.core import taskgraph as tg
from repro.models import moe as moe_lib
from repro.models.layers import mlp_apply
# module-level so the no-tracer walk pays no per-trace import lookup
from repro.obs.trace import active_tracer


def _mesh_prod(mesh, axes) -> int:
    p = 1
    for a in axes:
        p *= mesh.shape[a]
    return p


def as_exec_program(plan) -> tg.ExecProgram:
    """The executor's program for ``plan``: an ``ExecProgram`` passes
    through; a bare ``taskgraph.TaskGraph`` is wrapped with
    ``interleave="off"`` (the historical emission); a full ``Plan`` is
    lowered from its (r2, order, m_e) slice; None means the unchunked
    single-stream r2=1 schedule."""
    if plan is None:
        return tg.ExecProgram(tg.lower_exec(1, "AASS", 1))
    if isinstance(plan, tg.ExecProgram):
        return plan
    if isinstance(plan, tg.TaskGraph):
        return tg.ExecProgram(plan)
    r2 = max(int(getattr(plan, "r2", 1) or 1), 1)
    m_e = getattr(plan, "m_e", 1) or 1
    return tg.ExecProgram(tg.lower_exec(r2, getattr(plan, "order", "AASS"),
                                        max(int(m_e), 1)))


def as_exec_graph(plan) -> tg.TaskGraph:
    """The executor's task graph for ``plan`` (see ``as_exec_program``;
    this is its ``.graph`` view for callers that only need structure)."""
    return as_exec_program(plan).graph


def _shared_part(shared_fn, shared_x, k: int, n_seg: int):
    """The shared-expert GEMM for segment ``k`` of ``n_seg`` (the graph's
    SHARED task at stream-major segment index ``k``): each (mb, boundary)
    SHARED task owns one equal row range of the local batch; ASAS lowers
    r2 segments per stream, AASS one per stream. Rows of the shared GEMM
    are independent, so any segmentation concatenates back to the
    whole-batch product."""
    if n_seg == 1:
        return shared_fn(shared_x)
    seg = shared_x.shape[0] // n_seg
    lo = k * seg
    hi = shared_x.shape[0] if k == n_seg - 1 else (k + 1) * seg
    return shared_fn(shared_x[lo:hi])


def _walk_chunk_stream(program, handlers) -> None:
    """Emit ops for the program's executed order. ``program`` is a
    ``taskgraph.ExecProgram`` (a bare ``TaskGraph`` is accepted and means
    its single-stream ``interleave="off"`` walk); ``handlers`` maps task
    kind -> callable(task) returning the op group's value(s); missing
    kinds are skipped (e.g. SHARED for models without a shared expert).

    When a ``repro.obs`` tracer is scoped (``use_tracer``) around the
    caller, each handler call is wrapped in a task span (``emit=True``).
    Under jit the walk runs at jax trace time, so these spans record
    op-emission order and trace cost once per compiled program — NOT
    per-step execution time. With no active tracer (the default) this is
    the bare loop and the emitted program is identical."""
    if isinstance(program, tg.TaskGraph):
        program = tg.ExecProgram(program)
    tracer = active_tracer()
    if tracer is None:
        for task in program.walk():
            h = handlers.get(task.kind)
            if h is not None:
                h(task)
        return
    clock = tracer.clock
    for task in program.walk():
        h = handlers.get(task.kind)
        if h is not None:
            t0 = clock()
            h(task)
            tracer.task_span(task, t0, clock(), emit=True)


def _graph_expert_alltoall(program: tg.ExecProgram, buffers, expert_params,
                           axis: str, shared_fn=None, shared_x=None,
                           hot_weights=None, hot_rows=None):
    """Sequence-mode walk: buffers [E_pad, C_loc, M] per peer ->
    (outputs [E_pad, C_loc, M] back in dispatch layout, shared_out or
    None). Each A2E/EXP/E2A task becomes one chunk of the paper's
    dispatch -> expert FFN -> combine pipeline, in program order, so
    XLA's async collective scheduler can overlap transport with compute;
    SHARED tasks interleave at their lowered chunk boundaries.

    Task (stream i, chunk j) covers capacity columns
    [(i·r2+j)·c, (i·r2+j+1)·c) of the dispatch buffers — streams are a
    capacity split of ONE router dispatch, so the emitted values are
    independent of both the stream count and the emission order; the
    results reassemble in fixed (i, j) order. Under
    ``interleave="streams"`` the walk follows the scheduled start order
    (stream i+1's work issued before stream i retires); per-stream
    dispatch state lives in dicts keyed (mb, chunk), so each stream is
    naturally double-buffered — a stream's chunk buffer is dropped
    (donated) as soon as its consumer pops it, whatever the interleave.

    ``hot_weights``/``hot_rows`` realize the placement's REP task: the
    replicated hot experts' FFN runs on THIS peer's dispatch rows (the
    tokens are locally resident — no wire crossing) and the results
    overwrite the corresponding rows of the combined output; with r1
    streams each REP task runs its stream's capacity slice. Each
    (expert, capacity-slot) row of ``expert_ffn`` depends only on its
    own input row and the expert's weights, so the spliced rows are
    bit-identical to what the A2E -> EXP -> E2A round trip returns for
    them — replicas=0 therefore executes the exact unreplicated
    program."""
    graph = program.graph
    E_pad, C_loc, M = buffers.shape
    r1, r2 = graph.r1, graph.r2
    chunk = C_loc // (r1 * r2)
    n_seg = graph.shared_segments          # per stream
    rep_chunk = C_loc // r1                # REP slice width per stream
    dispatched = {}
    ffn_out = {}
    outs = {}
    shared_parts = {}
    hot_out = {}

    def on_a2e(t):     # [E_pad, c, M] -> [E_loc, mo*c, M]
        with jax.named_scope("moe/dispatch"):
            buf = jax.lax.dynamic_slice_in_dim(
                buffers, (t.mb * r2 + t.chunk) * chunk, chunk, 1)
            dispatched[(t.mb, t.chunk)] = jax.lax.all_to_all(
                buf, axis, split_axis=0, concat_axis=1, tiled=True)
        return dispatched[(t.mb, t.chunk)]

    def on_shared(t):
        if shared_fn is None:
            return None
        with jax.named_scope("moe/shared_expert"):
            part = _shared_part(shared_fn, shared_x,
                                t.mb * n_seg + t.chunk, r1 * n_seg)
        shared_parts[(t.mb, t.chunk)] = part
        return part

    def on_exp(t):
        out = moe_lib.expert_ffn(expert_params,
                                 dispatched.pop((t.mb, t.chunk)))
        ffn_out[(t.mb, t.chunk)] = out
        return out

    def on_e2a(t):     # [E_loc, mo*c, M] -> [E_pad, c, M]
        with jax.named_scope("moe/combine"):
            out = jax.lax.all_to_all(ffn_out.pop((t.mb, t.chunk)), axis,
                                     split_axis=1, concat_axis=0,
                                     tiled=True)
        outs[(t.mb, t.chunk)] = out
        return out

    def on_rep(t):     # hot-expert FFN on the locally resident rows
        rows = jax.lax.dynamic_slice_in_dim(
            buffers[hot_rows], t.mb * rep_chunk, rep_chunk, 1)
        hot_out[t.mb] = moe_lib.expert_ffn(hot_weights, rows)
        return hot_out[t.mb]

    handlers = {tg.A2E: on_a2e, tg.SHARED: on_shared,
                tg.EXP: on_exp, tg.E2A: on_e2a}
    if hot_weights is not None:
        handlers[tg.REP] = on_rep
    _walk_chunk_stream(program, handlers)
    if hot_weights is not None and not hot_out:
        # plan graph lowered without a REP task (e.g. a stale epoch-0
        # graph): still execute the hot FFN, after the chunk stream
        hot_out[0] = moe_lib.expert_ffn(hot_weights, buffers[hot_rows])
    shared_out = (jnp.concatenate([shared_parts[k]
                                   for k in sorted(shared_parts)], axis=0)
                  if shared_parts else None)
    out = jnp.concatenate([outs[k] for k in sorted(outs)], axis=1)
    if hot_out:
        hot = jnp.concatenate([hot_out[k] for k in sorted(hot_out)], axis=1)
        out = out.at[hot_rows].set(hot)
    return out, shared_out


def _graph_replicated_experts(program: tg.ExecProgram, local_buf,
                              expert_params, shared_fn=None, shared_x=None):
    """Replicated-token decode walk: each peer runs only its local
    experts' chunks; A2E tasks become buffer slices (the transport is the
    single psum combine after the walk, realized by the caller at the
    E2A position) and SHARED tasks interleave per the solved order. The
    same (stream, chunk) capacity split and fixed-order reassembly as
    the sequence walk."""
    graph = program.graph
    cap = local_buf.shape[1]
    r1, r2 = graph.r1, graph.r2
    chunk = cap // (r1 * r2)
    n_seg = graph.shared_segments
    sliced = {}
    outs = {}
    shared_parts = {}

    def on_a2e(t):
        with jax.named_scope("moe/dispatch"):
            sliced[(t.mb, t.chunk)] = jax.lax.dynamic_slice_in_dim(
                local_buf, (t.mb * r2 + t.chunk) * chunk, chunk, 1)
        return sliced[(t.mb, t.chunk)]

    def on_shared(t):
        if shared_fn is None:
            return None
        with jax.named_scope("moe/shared_expert"):
            part = _shared_part(shared_fn, shared_x,
                                t.mb * n_seg + t.chunk, r1 * n_seg)
        shared_parts[(t.mb, t.chunk)] = part
        return part

    def on_exp(t):
        out = moe_lib.expert_ffn(expert_params,
                                 sliced.pop((t.mb, t.chunk)))
        outs[(t.mb, t.chunk)] = out
        return out

    _walk_chunk_stream(program, {tg.A2E: on_a2e, tg.SHARED: on_shared,
                                 tg.EXP: on_exp})
    shared_out = (jnp.concatenate([shared_parts[k]
                                   for k in sorted(shared_parts)], axis=0)
                  if shared_parts else None)
    out = jnp.concatenate([outs[k] for k in sorted(outs)], axis=1)
    return out, shared_out


def _token_layout(mesh, axis: str, B: int, S: int):
    """How ``moe_apply_dep`` splits a [B, S] input: (``seq_mode``, the
    data axes that shard the batch, and the tokens each shard routes).
    Sequence mode splits S over the expert axis; otherwise tokens are
    replicated over it."""
    data_axes = tuple(a for a in mesh.axis_names if a != axis)
    mo = mesh.shape[axis]
    seq_mode = S % mo == 0 and S >= mo
    dp = _mesh_prod(mesh, data_axes)
    b_shard = data_axes if (B % dp == 0 and B >= dp) else ()
    t_loc = (B // _mesh_prod(mesh, b_shard)) * (S // mo if seq_mode else S)
    return seq_mode, b_shard, t_loc


def expert_rows(B: int, S: int, mcfg: MoEConfig, ctx,
                num_experts_padded: int, plan=None,
                capacity_scale: float = 1.0) -> int:
    """Rows one DEP layer's routed-expert FFN computes for a [B, S] input
    over the whole mesh, padding included: each token shard's [E_pad, C]
    dispatch buffers, C sized as ``moe_apply_dep`` sizes it."""
    mesh = ctx.mesh
    seq_mode, b_shard, t_loc = _token_layout(mesh, ctx.expert_axis, B, S)
    graph = as_exec_program(plan).graph
    E_pad = num_experts_padded or mcfg.num_experts
    cap = moe_lib.expert_capacity(t_loc, mcfg, E_pad,
                                  multiple_of=graph.r1 * graph.r2
                                  * graph.m_e,
                                  scale=capacity_scale)
    shards = _mesh_prod(mesh, b_shard) * (
        mesh.shape[ctx.expert_axis] if seq_mode else 1)
    return shards * E_pad * cap


def moe_apply_dep(params, x, mcfg: MoEConfig, ctx, num_experts_padded: int,
                  plan=None, placement=None, return_stats: bool = False,
                  capacity_scale: float = 1.0):
    """Schedule-driven MoE layer. x: [B, S, M] (global view). ``ctx`` is a
    repro.models.transformer.ExecutionContext carrying the mesh; ``plan``
    is the schedule resolved by a repro.sched.SchedulePolicy for the
    current shape — a ``taskgraph.ExecProgram`` (preferred; see
    ``Plan.exec_program``), a bare ``TaskGraph`` (historical single-
    stream emission), a ``Plan`` (lowered here), or None (the unchunked
    r2=1 schedule).

    ``placement`` is an optional ``repro.placement.Placement`` over the
    PADDED expert dimension: its ``perm`` re-homes each logical expert's
    dispatch to the physical buffer row where the (engine-permuted)
    weights live, and its replicated hot experts execute the REP task —
    their FFN runs on the locally resident dispatch rows in sequence
    mode, bit-identically splicing over the wire round trip. ``None`` or
    the uniform no-replica placement takes exactly the legacy path.
    ``return_stats`` appends a ``moe.MoEStats`` (global [E] logical load
    histogram + dropped-assignment count) to the return.
    ``capacity_scale`` (static float >= 1) widens the dispatch capacity
    to the observed hottest-expert load (see
    ``placement.capacity_scale``); 1.0 is the legacy uniform sizing."""
    mesh = ctx.mesh
    assert mesh is not None, "DEP impl needs a mesh"
    axis = ctx.expert_axis
    B, S, M = x.shape
    mo = mesh.shape[axis]
    E_pad = num_experts_padded or mcfg.num_experts
    assert E_pad % mo == 0, (E_pad, mo)
    program = as_exec_program(plan)
    graph = program.graph
    if placement is not None and placement.is_uniform:
        placement = None        # the legacy path IS this placement
    if placement is not None:
        assert placement.num_experts == E_pad, \
            (placement.num_experts, E_pad)
        assert placement.num_ranks == mo, (placement.num_ranks, mo)
    # the solver's per-expert chunk granularity: align the capacity so
    # each of the r1·r2 (stream, chunk) slices is a multiple of the m_e
    # the solver modeled (Eq. 3), not merely slice-count-divisible.
    # Capacity only ever rounds UP, so drops never increase and
    # schedule-free callers (m_e hint absent -> 1) are unchanged.
    cap_multiple = graph.r1 * graph.r2 * graph.m_e

    seq_mode, b_shard, _ = _token_layout(mesh, axis, B, S)
    n_devices = _mesh_prod(mesh, mesh.axis_names)

    has_shared = "shared" in params
    in_spec = P(b_shard or None, axis if seq_mode else None, None)
    expert_spec = jax.tree.map(lambda _: P(axis, None, None),
                               params["experts"])
    router_spec = jax.tree.map(lambda _: P(), params["router"])
    specs = [in_spec, router_spec, expert_spec]
    args = [x, params["router"], params["experts"]]
    if has_shared:
        specs.append(jax.tree.map(lambda _: P(), params["shared"]))
        args.append(params["shared"])

    # placement: the logical->physical dispatch map, plus the replicated
    # hot experts' rows and weights (gathered from the GLOBAL stacked
    # arrays here, replicated to every peer — that IS the replication)
    expert_map = None
    hot_rows = None
    hot_weights = None
    if placement is not None:
        expert_map = jnp.asarray(placement.perm, jnp.int32)
        specs.append(P())
        args.append(expert_map)
        if placement.hot_experts and seq_mode:
            perm = placement.perm
            hot_rows = jnp.asarray([perm[e] for e in placement.replicated],
                                   jnp.int32)
            hot_weights = jax.tree.map(lambda a: a[hot_rows],
                                       params["experts"])
            specs.extend([P(), jax.tree.map(lambda _: P(), hot_weights)])
            args.extend([hot_rows, hot_weights])

    all_axes = tuple(mesh.axis_names)
    # axes that actually shard tokens: psum over them recovers the GLOBAL
    # load/drop counts on every device (the rest only replicate tokens)
    tok_axes = (b_shard or ()) + ((axis,) if seq_mode else ())

    def local(x_loc, router_loc, experts_loc, *rest):
        rest = list(rest)
        shared_loc = rest.pop(0) if has_shared else None
        emap_loc = rest.pop(0) if expert_map is not None else None
        hrows_loc = rest.pop(0) if hot_rows is not None else None
        hw_loc = rest.pop(0) if hot_weights is not None else None
        Bl, Sl, _ = x_loc.shape
        xf = x_loc.reshape(-1, M)
        T_loc = xf.shape[0]
        # the walk's GATE task: router dispatch into capacity buffers.
        # capacity_scale widens the buffers to the observed hottest-expert
        # load (skew-aware planning) — 1.0 is the legacy uniform sizing.
        cap = moe_lib.expert_capacity(T_loc, mcfg, E_pad,
                                      multiple_of=cap_multiple,
                                      scale=capacity_scale)
        info = moe_lib.moe_dispatch({"router": router_loc}, xf, mcfg, cap,
                                    E_pad, expert_map=emap_loc)
        stats = None
        if return_stats:
            if tok_axes:
                load = jax.lax.psum(info.load, tok_axes)
                dropped = jax.lax.psum(info.dropped, tok_axes)
            else:
                load, dropped = info.load, info.dropped
            stats = moe_lib.MoEStats(load=load, dropped=dropped)
        shared_fn = (None if shared_loc is None
                     else (lambda xs: mlp_apply(shared_loc, xs)))
        if seq_mode:
            out, shared_out = _graph_expert_alltoall(
                program, info.buffers, experts_loc, axis,
                shared_fn=shared_fn, shared_x=xf,
                hot_weights=hw_loc, hot_rows=hrows_loc)
        else:
            # replicated-token decode path; the shared expert interleaves
            # with the chunk stream per the SOLVED order (the graph's
            # SHARED boundary indices, same lowering as the sequence path)
            mo_idx = jax.lax.axis_index(axis)
            E_loc = E_pad // mo
            with jax.named_scope("moe/dispatch"):
                local_buf = jax.lax.dynamic_slice_in_dim(
                    info.buffers, mo_idx * E_loc, E_loc, 0)
            local_out, shared_out = _graph_replicated_experts(
                program, local_buf, experts_loc,
                shared_fn=shared_fn, shared_x=xf)   # [E_loc, cap, M]
            # expert-local combine (the walk's E2A tasks): each peer
            # combines only ITS experts' contributions into the dense
            # [T, M] output and the transport is a psum of that —
            # (E_pad*cap)/T ~ top_k*cf times fewer bytes than psum-ing
            # the padded dispatch buffers.
            with jax.named_scope("moe/combine"):
                pad = jnp.zeros((E_pad - E_loc,) + local_out.shape[1:],
                                local_out.dtype)
                out_local_layout = jnp.roll(
                    jnp.concatenate([local_out, pad], axis=0),
                    mo_idx * E_loc, axis=0)
            y_partial = moe_lib.moe_combine(info, out_local_layout, T_loc,
                                            x_loc.dtype)
            with jax.named_scope("moe/combine"):
                y = jax.lax.psum(y_partial, axis)
            if shared_out is not None:
                y = y + shared_out
            aux = jax.lax.psum(info.aux, all_axes) / n_devices
            y = y.reshape(Bl, Sl, M)
            return (y, aux, stats) if return_stats else (y, aux)
        y = moe_lib.moe_combine(info, out, T_loc, x_loc.dtype)
        if shared_out is not None:
            y = y + shared_out
        # device-mean: exact over distinct shards, unbiased under replication
        aux = jax.lax.psum(info.aux, all_axes) / n_devices
        y = y.reshape(Bl, Sl, M)
        return (y, aux, stats) if return_stats else (y, aux)

    out_specs = (in_spec, P())
    if return_stats:
        out_specs += (moe_lib.MoEStats(load=P(), dropped=P()),)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=tuple(specs),
        out_specs=out_specs,
        check_vma=False,
    )(*args)
