"""Parameter / activation partition rules per architecture family × mesh.

Rules are name-based over the parameter pytree paths produced by
``repro.models``:

  dense / moe / vlm / audio (attention stacks):
    column-parallel (shard output dim over "model"): wq wk wv w_kv_b gate up
        ffn_up w_gates w_if skip lm_head proj
    row-parallel   (shard input dim over "model"):  wo down out ffn_down
    expert-parallel (shard expert dim):             experts.{gate,up,down}
    vocab-sharded:                                  embed.embedding
    replicated: norms, biases of row-parallel, router, small MLA latents
  ssm (xLSTM): weights replicated (matrix-memory recurrence does not
    shard over d_inner without cross-device outer products); batch DP.
  hybrid (RG-LRU): recurrence width W is elementwise => column-parallel
    in-projections, sharded state, row-parallel out.

Stacked-layer params (scan mode) get the same spec with a leading None.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig

COL = ("wq", "wk", "wv", "w_kv_b", "gate", "up", "ffn_up", "w_gates",
       "w_if", "skip", "lm_head", "proj", "in_x", "in_gate", "w_a", "w_i")
ROW = ("wo", "down", "out", "ffn_down")
# "shared" experts replicate: in DEP they belong to the (data-parallel) AG
REPL = ("w_kv_a", "router", "r_gates", "conv", "shared")


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for e in path:
        if hasattr(e, "key"):
            names.append(str(e.key))
        elif hasattr(e, "idx"):
            names.append(f"[{e.idx}]")
        else:
            names.append(str(e))
    return tuple(names)


def _spec_for(names: Tuple[str, ...], leaf, cfg: ModelConfig,
              model_axis: str) -> P:
    nd = leaf.ndim
    joined = set(names)

    def pad_left(spec_tail):
        """Left-pad with None for any stacking/extra leading dims."""
        pad = nd - len(spec_tail)
        return P(*([None] * pad + list(spec_tail)))

    if "embedding" in joined:
        return pad_left([model_axis, None])
    # SSM family: replicate everything but the embedding/readout
    if cfg.family == "ssm":
        if any(n in joined for n in ("lm_head",)):
            return pad_left([None, model_axis])
        return P(*([None] * nd))
    if "experts" in joined:
        return pad_left([model_axis, None, None])
    last = None
    for n in names:
        if n in REPL:
            return P(*([None] * nd))
    for n in names:
        if n in ROW and nd >= 2:
            return pad_left([model_axis, None])
    for n in names:
        if n in COL:
            if nd >= 2:
                return pad_left([None, model_axis])
            return pad_left([model_axis])           # col-parallel bias
    return P(*([None] * nd))


def sanitize_spec(spec: P, shape, mesh: Optional[Mesh]) -> P:
    """Drop axis assignments whose mesh extent does not divide the dim —
    jit in_shardings require exact divisibility (no GSPMD padding)."""
    if mesh is None:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        prod = 1
        for a in axes:
            prod *= mesh.shape[a]
        out.append(entry if dim % prod == 0 else None)
    return P(*out)


FSDP_THRESHOLD_ELEMS = 8 * 1024 * 1024    # shard-further above 16MB bf16


def apply_fsdp(spec: P, shape, mesh: Optional[Mesh],
               fsdp_axis: str = "data",
               threshold_elems: int = FSDP_THRESHOLD_ELEMS) -> P:
    """ZeRO-3-style 2D weight sharding: when a parameter is still larger
    than FSDP_THRESHOLD_ELEMS per device after tensor sharding, shard its
    largest unsharded dim over the data axis too (GSPMD all-gathers it just
    before use). Intra-pod only — never over "pod" (DCI too slow)."""
    if mesh is None or fsdp_axis not in mesh.axis_names or len(shape) < 2:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    shards = 1
    for e in entries:
        if e is None:
            continue
        for a in ((e,) if isinstance(e, str) else e):
            shards *= mesh.shape[a]
    elems = 1
    for d in shape:
        elems *= d
    if elems // shards <= threshold_elems:
        return spec
    df = mesh.shape[fsdp_axis]
    for dim in sorted(range(len(shape)), key=lambda i: -shape[i]):
        if entries[dim] is None and shape[dim] % df == 0:
            entries[dim] = fsdp_axis
            return P(*entries)
    return spec


# Never FSDP the readout/embedding: sharding their contracting dim makes
# GSPMD gather the [tokens, vocab] logits (observed ~1 TB at train_4k with
# a 256k vocab) instead of the (small) weight.
FSDP_EXCLUDE = ("embedding", "lm_head")


def params_pspecs(params, cfg: ModelConfig, model_axis: str = "model",
                  mesh: Optional[Mesh] = None, fsdp: bool = True,
                  fsdp_threshold_elems: int = FSDP_THRESHOLD_ELEMS):
    """PartitionSpec pytree matching ``params``."""
    def one(path, leaf):
        names = _path_names(path)
        spec = _spec_for(names, leaf, cfg, model_axis)
        spec = sanitize_spec(spec, leaf.shape, mesh)
        if fsdp and not any(n in FSDP_EXCLUDE for n in names):
            spec = apply_fsdp(spec, leaf.shape, mesh,
                              threshold_elems=fsdp_threshold_elems)
        return spec
    return jax.tree_util.tree_map_with_path(one, params)


def params_shardings(params, cfg: ModelConfig, mesh: Mesh,
                     model_axis: str = "model"):
    specs = params_pspecs(params, cfg, model_axis, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)


def expert_parallel_shardings(params, mesh: Mesh, model_axis: str = "model"):
    """The serving placement: routed experts' weights split over
    ``model_axis`` on their expert dim (the layout ``core.dep``'s
    shard_map expects), every other parameter replicated. ``params`` may
    be arrays or ``jax.eval_shape`` structs."""
    def one(path, leaf):
        if "experts" in _path_names(path):
            # [..., E, in, out]: any leading dims are layer stacking
            return NamedSharding(
                mesh, P(*([None] * (leaf.ndim - 3)), model_axis, None, None))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map_with_path(one, params)


def batch_pspec(global_batch: int, mesh: Mesh,
                exclude: Tuple[str, ...] = ("model",)) -> P:
    """Shard the batch dim over as many data axes as divide it."""
    axes = []
    prod = 1
    for a in mesh.axis_names:
        if a in exclude:
            continue
        if global_batch % (prod * mesh.shape[a]) == 0:
            axes.append(a)
            prod *= mesh.shape[a]
    return P(tuple(axes) or None)


def cache_pspecs(cache, cfg: ModelConfig, mesh: Mesh, global_batch: int,
                 model_axis: str = "model", stacked: bool = False):
    """KV caches: batch over data axes, kv-heads over model (GSPMD pads
    when they do not divide); SSM states: batch over data axes, width over
    model for RG-LRU. ``stacked`` marks scan-mode caches with a leading
    layer-group dimension (left-padded with None)."""
    bspec = batch_pspec(global_batch, mesh)
    b_axes = bspec[0] if bspec != P(None) else None
    lead = [None] if stacked else []

    def spec(path, leaf):
        names = _path_names(path)
        nd = leaf.ndim - (1 if stacked else 0)
        if nd <= 0:                         # cache index scalar
            return P(*lead) if stacked else P()
        if any(n in ("k", "v") for n in names) and nd == 4:
            kv, C = leaf.shape[-3], leaf.shape[-2]     # head-major
            mo = mesh.shape[model_axis]
            if kv % mo == 0:    # kv-head sharding when it divides
                return P(*lead, b_axes, model_axis, None, None)
            if C % mo == 0:     # else sequence-sharded: served by the
                                # shard_map distributed-flash decode core
                return P(*lead, b_axes, None, model_axis, None)
            return P(*lead, b_axes, None, None, None)
        if "h" in names and nd == 2 and cfg.family == "hybrid":
            return P(*lead, b_axes, model_axis)
        # ssm states / conv states: batch-sharded only
        return P(*(lead + [b_axes] + [None] * (nd - 1)))

    return jax.tree_util.tree_map_with_path(spec, cache)
