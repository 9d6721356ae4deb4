"""Roofline terms from a compiled dry-run artifact.

    compute term    = HLO_FLOPs_per_device     / peak_FLOP/s
    memory term     = HLO_bytes_per_device     / HBM_bw
    collective term = wire_bytes_per_device    / link_bw

``compiled.cost_analysis()`` reports the SPMD per-device program, so the
terms above are per-device times; they equal the assignment's
"global / (chips * peak)" formulation because global = per_device * chips.

collective bytes are NOT in cost_analysis: we parse the post-partitioning
HLO (``compiled.as_text()``) and sum wire traffic for every collective:

    all-gather          result_bytes  * (N-1)/N
    reduce-scatter      operand_bytes * (N-1)/N
    all-reduce          2 * operand_bytes * (N-1)/N      (ring)
    all-to-all          operand_bytes * (N-1)/N
    collective-permute  operand_bytes

Hardware constants (TPU v5e): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")


def _shape_bytes(tok: str) -> int:
    m = _SHAPE_RE.match(tok)
    if not m:
        return 0
    dt, dims = m.groups()
    if dt == "token" or dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def _tuple_or_shape_bytes(text: str) -> int:
    """Sum bytes of all array shapes in a type string (handles tuples)."""
    return sum(_shape_bytes(m.group(0))
               for m in _SHAPE_RE.finditer(text))


@dataclass
class CollectiveStats:
    counts: Dict[str, int] = field(default_factory=dict)
    wire_bytes: Dict[str, float] = field(default_factory=dict)

    @property
    def total_bytes(self) -> float:
        return sum(self.wire_bytes.values())

    @property
    def total_count(self) -> int:
        return sum(self.counts.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Parse post-SPMD HLO and accumulate per-device wire bytes."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        s = line.strip()
        # async pairs appear as -start/-done; count -start only. Fused
        # sync ops appear bare.
        kind = None
        for k in COLLECTIVE_KINDS:
            if re.search(rf"(?<![\w-]){re.escape(k)}(-start)?\(", s):
                if f"{k}-done" in s:
                    kind = None
                else:
                    kind = k
                break
        if kind is None:
            continue
        # result type: between "= " and the op name
        m = re.search(r"=\s+(.*?)\s+" + re.escape(kind), s)
        result_bytes = _tuple_or_shape_bytes(m.group(1)) if m else 0
        # operand types: inside the call parens. Modern HLO prints operands
        # WITHOUT inline types ("all-reduce(%x)"), in which case we infer
        # from the result type: all-reduce / all-to-all / collective-permute
        # preserve shape; reduce-scatter's operand is result * N.
        m2 = re.search(re.escape(kind) + r"(?:-start)?\((.*?)\)", s)
        operand_bytes = _tuple_or_shape_bytes(m2.group(1)) if m2 else 0
        # group size N
        N = 1
        g = _GROUPS_RE.search(s)
        if g:
            N = len([x for x in g.group(1).split(",") if x.strip() != ""])
        else:
            gi = _IOTA_GROUPS_RE.search(s)
            if gi:
                N = int(gi.group(2))
        frac = (N - 1) / N if N > 1 else 0.0
        if operand_bytes == 0:               # untyped operands: infer
            operand_bytes = (result_bytes * N if kind == "reduce-scatter"
                             else result_bytes)
        if kind == "all-gather":
            wire = result_bytes * frac
        elif kind == "reduce-scatter":
            wire = operand_bytes * frac
        elif kind == "all-reduce":
            wire = 2.0 * operand_bytes * frac
        elif kind == "all-to-all":
            wire = operand_bytes * frac
        else:  # collective-permute
            wire = operand_bytes
        stats.counts[kind] = stats.counts.get(kind, 0) + 1
        stats.wire_bytes[kind] = stats.wire_bytes.get(kind, 0.0) + wire
    return stats


@dataclass
class Roofline:
    name: str
    flops_per_device: float
    bytes_per_device: float
    collective_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float           # 6*N*D (active params) global
    useful_ratio: float          # MODEL_FLOPS / (HLO_FLOPs * chips)
    collectives: CollectiveStats = None
    peak_memory_bytes: float = 0.0

    def as_row(self) -> dict:
        return dict(name=self.name,
                    compute_ms=self.compute_s * 1e3,
                    memory_ms=self.memory_s * 1e3,
                    collective_ms=self.collective_s * 1e3,
                    dominant=self.dominant,
                    useful_ratio=self.useful_ratio,
                    peak_mem_gb=self.peak_memory_bytes / 1e9)


def analyze(name: str, compiled, model_flops_global: float, chips: int,
            peak_flops: float = PEAK_FLOPS, hbm_bw: float = HBM_BW,
            link_bw: float = LINK_BW) -> Roofline:
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    stats = parse_collectives(compiled.as_text())
    compute_s = flops / peak_flops
    memory_s = byts / hbm_bw
    coll_s = stats.total_bytes / link_bw
    dominant = max(
        (("compute", compute_s), ("memory", memory_s),
         ("collective", coll_s)), key=lambda kv: kv[1])[0]
    useful = model_flops_global / max(flops * chips, 1.0)
    try:
        ma = compiled.memory_analysis()
        peak = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.output_size_in_bytes)
    except Exception:
        peak = 0.0
    return Roofline(name=name, flops_per_device=flops,
                    bytes_per_device=byts,
                    collective_bytes=stats.total_bytes,
                    compute_s=compute_s, memory_s=memory_s,
                    collective_s=coll_s, dominant=dominant,
                    model_flops=model_flops_global, useful_ratio=useful,
                    collectives=stats, peak_memory_bytes=peak)


def scan_corrections(cfg, shape, dp_shards: int, mode: str,
                     q_chunk: int = 512, k_chunk: int = 1024) -> Dict[str, float]:
    """Analytic per-device counts hidden inside lax.scan bodies (XLA's
    cost_analysis counts a While body ONCE regardless of trip count).

    Two scan families need correction in the count-probes:
      * chunked (flash-style) attention: outer q-chunk scan x inner k-chunk
        scan -> counted 1/(nq*nk) of the pair grid;
      * xLSTM time recurrences (mLSTM/sLSTM): counted 1/S of the steps.
    RG-LRU uses associative_scan (fully unrolled in HLO, counted exactly).
    Training multiplies by 3 (fwd + ~2x bwd, the scan bodies are also
    differentiated into scans). Returns extra {"flops", "bytes"} per device.
    """
    from repro.models.transformer import layer_kinds
    kinds = layer_kinds(cfg)
    B_dev = max(shape.global_batch // max(dp_shards, 1), 1)
    S = shape.seq_len if mode in ("train", "prefill") else 1
    mult = 3.0 if mode == "train" else 1.0
    extra_flops = 0.0
    extra_bytes = 0.0
    if S <= 1:
        return {"flops": 0.0, "bytes": 0.0}

    n_attn = sum(1 for k in kinds if k in ("attn_mlp", "attn_moe", "attn"))
    if n_attn and cfg.uses_attention and S > 2048:
        H, D = cfg.num_heads, cfg.head_dim
        Kv = cfg.num_kv_heads
        nq = (S + q_chunk - 1) // q_chunk
        nk = (S + k_chunk - 1) // k_chunk
        fl = 4.0 * B_dev * H * S * S * D           # QK^T + PV (impl, no
        fl *= (1.0 - 1.0 / (nq * nk))              # causal skipping)
        by = (nq * 2.0 * S * Kv * D + 2.0 * S * H * D) * 2.0 * B_dev
        extra_flops += n_attn * fl * mult
        extra_bytes += n_attn * by * mult

    n_mlstm = sum(1 for k in kinds if k == "mlstm")
    if n_mlstm:
        hd = 2 * cfg.d_model // cfg.num_heads      # d_inner / H
        per_step_fl = 8.0 * B_dev * cfg.num_heads * hd * hd
        per_step_by = 2.0 * 4.0 * B_dev * cfg.num_heads * hd * hd
        extra_flops += n_mlstm * per_step_fl * (S - 1) * mult
        extra_bytes += n_mlstm * per_step_by * (S - 1) * mult

    n_slstm = sum(1 for k in kinds if k == "slstm")
    if n_slstm:
        M = cfg.d_model
        hd = M // cfg.num_heads
        per_step_fl = 8.0 * B_dev * M * hd + 30.0 * B_dev * M
        per_step_by = 4.0 * M * hd * 4.0           # r_gates re-read
        extra_flops += n_slstm * per_step_fl * (S - 1) * mult
        extra_bytes += n_slstm * per_step_by * (S - 1) * mult

    return {"flops": extra_flops, "bytes": extra_bytes}


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6 * N_active * D tokens (training: *3 for fwd+bwd...
    we follow the assignment: 6*N*D counts fwd+bwd; for inference steps we
    use 2*N*D forward-only)."""
    n_active = cfg.active_params()
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch
