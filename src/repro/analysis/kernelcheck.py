"""Bounds checker for the Pallas ``index_map``s — no kernel launch.

A Pallas ``index_map`` is pure integer math from grid coordinates (plus
scalar-prefetched operands) to a block index; an out-of-range result is
an out-of-bounds HBM stream the interpreter may mask and real hardware
will not. This pass evaluates the PRODUCTION index_maps (the module-
level builders the kernels themselves install: ``dense_kv_index_map``,
``paged_kv_index_map``, ``flash_kv_index_map``) over the full grid for a
ledger of boundary states — length 0, lengths straddling a block edge,
non-dividing C (the ``largest_block_size`` fallback), full block tables,
-1 (unallocated) tail entries — and proves every emitted block index
lands inside the operand's block grid. The paged states come from a real
ledger-only ``PagedKVCacheManager`` (shared prefixes, decode growth), so
the tables checked are the tables the serving path builds.

Beyond raw range checks the pass verifies the *semantic* contracts the
flash bodies rely on:

  * dense: an in-length step c (c*bc < len) maps to block c itself —
    clamping must never redirect a live step;
  * paged: an in-length step dereferences exactly ``table[b, c]`` and
    that entry is an allocated non-scratch page; only past-length /
    unallocated steps may land on the scratch page 0;
  * flash: program bh reads KV row (bh // H) * Kv + (bh % H) // g — the
    GQA fold stays inside the flattened [B*Kv] operand and is constant
    across the g query heads of one KV group.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.analysis.report import Violation

PASS = "kernelcheck"


def _ints(tup) -> Tuple[int, ...]:
    """Concretize an index_map result (jnp scalars on CPU) to ints."""
    return tuple(int(x) for x in tup)


# ---------------------------------------------------------------------------
# dense decode: grid (B, Kv, C // bc), k/v [B, Kv, C, D] block (1, 1, bc, D)
# ---------------------------------------------------------------------------


def check_dense_index_map(C: int, bc: int, lengths,
                          Kv: int = 2) -> List[Violation]:
    """Evaluate the dense decode K/V index_map over the full grid for one
    lengths vector (clipped to [0, C] exactly as the wrapper does)."""
    from repro.kernels.decode_attention.kernel import (dense_kv_index_map,
                                                       largest_block_size)
    out: List[Violation] = []
    bc = largest_block_size(C, bc)
    n_c = C // bc
    lens = np.clip(np.asarray(lengths, np.int32), 0, C)
    B = lens.shape[0]
    kv_map = dense_kv_index_map(bc)
    where = f"dense(C={C}, bc={bc}, lens={lens.tolist()})"
    for b in range(B):
        for kv in range(Kv):
            for c in range(n_c):
                bi, kvi, ci, di = _ints(kv_map(b, kv, c, lens))
                if not (bi == b and kvi == kv and di == 0):
                    out.append(Violation(
                        PASS, "dense-block-identity", where,
                        f"grid ({b},{kv},{c}) mapped row/head "
                        f"({bi},{kvi},{di}), expected ({b},{kv},0)"))
                if not 0 <= ci < n_c:
                    out.append(Violation(
                        PASS, "dense-block-range", where,
                        f"grid ({b},{kv},{c}) emits context block {ci} "
                        f"outside [0, {n_c})"))
                elif c * bc < lens[b] and ci != c:
                    out.append(Violation(
                        PASS, "dense-live-step-redirected", where,
                        f"in-length step {c} (len={int(lens[b])}) was "
                        f"clamped to block {ci}; live steps must stream "
                        f"their own block"))
    return out


#: boundary lengths for a (C, bc) case: empty row, one token, both sides
#: of the first block edge, and both sides of the cache capacity.
def _boundary_lengths(C: int, bc: int) -> List[int]:
    cand = [0, 1, bc - 1, bc, bc + 1, C - 1, C, C + 7]
    return sorted({max(min(v, C + 7), 0) for v in cand})


# ---------------------------------------------------------------------------
# paged decode: grid (B, Kv, n_blocks), k/v pools [P, Kv, bs, D]
# ---------------------------------------------------------------------------


def check_paged_index_map(tables, lengths, num_pages: int, bs: int,
                          Kv: int = 2, where: str = "",
                          scratch_page: int = 0) -> List[Violation]:
    """Evaluate the paged K/V index_map over the full grid for one
    (block table, lengths) ledger state. ``tables`` is int [B, n_blocks]
    (< 0 = unallocated); every emitted page must lie in [0, num_pages),
    in-length steps must dereference their own allocated table entry,
    and only dead steps may fall through to the scratch page."""
    from repro.kernels.decode_attention.kernel import paged_kv_index_map
    out: List[Violation] = []
    tbl = np.asarray(tables, np.int32)
    B, n_blocks = tbl.shape
    C = n_blocks * bs
    lens = np.clip(np.asarray(lengths, np.int32), 0, C)
    kv_map = paged_kv_index_map(bs)
    where = where or f"paged(P={num_pages}, bs={bs}, B={B})"
    for b in range(B):
        for kv in range(Kv):
            for c in range(n_blocks):
                pi, kvi, off, di = _ints(kv_map(b, kv, c, lens, tbl))
                if not (off == 0 and kvi == kv and di == 0):
                    out.append(Violation(
                        PASS, "paged-block-identity", where,
                        f"grid ({b},{kv},{c}) mapped offsets "
                        f"({kvi},{off},{di}), expected ({kv},0,0)"))
                if not 0 <= pi < num_pages:
                    out.append(Violation(
                        PASS, "paged-page-range", where,
                        f"grid ({b},{kv},{c}) emits page {pi} outside "
                        f"[0, {num_pages}) (table entry "
                        f"{int(tbl[b, c])}, len={int(lens[b])})"))
                    continue
                if c * bs < lens[b]:
                    want = int(tbl[b, c])
                    if want < 0:
                        out.append(Violation(
                            PASS, "paged-live-step-unallocated", where,
                            f"row {b} len={int(lens[b])}: in-length "
                            f"block {c} has no page (table entry -1) — "
                            f"the ledger promised coverage it did not "
                            f"allocate"))
                    elif pi != want:
                        out.append(Violation(
                            PASS, "paged-live-step-redirected", where,
                            f"row {b} in-length block {c} streamed page "
                            f"{pi}, table says {want}"))
                    elif pi == scratch_page:
                        out.append(Violation(
                            PASS, "paged-live-step-scratch", where,
                            f"row {b} in-length block {c} mapped to the "
                            f"reserved scratch page {scratch_page}"))
    return out


def _ledger_states(bs: int = 16):
    """Boundary ledger states from a REAL ledger-only manager: shared
    prefixes, partial tails, a full-table row, decode growth, a freshly
    reset slot, and a never-touched (all -1, length 0) slot. Returns
    (manager, synthetic_extra_states)."""
    from repro.runtime.paging import PagedKVCacheManager
    kv = PagedKVCacheManager(6, max_context=4 * bs, block_size=bs,
                             num_blocks=24)
    base = list(range(2 * bs))                  # two shareable full blocks
    kv.assign_blocks(0, base + [7] * 3)         # prefix + partial tail
    kv.set_length(0, 2 * bs + 4)
    kv.assign_blocks(1, base + [9] * (bs + 1))  # shares slot 0's prefix
    kv.set_length(1, 3 * bs + 2)
    kv.assign_blocks(2, list(range(4 * bs - 1)))   # full table row
    kv.set_length(2, 4 * bs)
    kv.assign_blocks(3, [5] * bs)               # prompt fills block 0
    kv.set_length(3, bs + 1)                    # next write is block 1
    kv.ensure_decode_page(3)                    # decode-growth tail page
    kv.reset_slot(4)                            # recovered slot, len 1
    # slot 5 never allocated: all -1, length 0
    return kv


def run(fast: bool = False, log=None) -> Tuple[List[Violation], Dict]:
    """All three kernels over their case matrices."""
    out: List[Violation] = []
    cases = 0

    # dense: dividing, non-dividing (largest_block_size falls to a
    # smaller tile-aligned divisor), single-block, and prime-C single-block
    dense_shapes = [(64, 16), (40, 16), (16, 512), (13, 512)]
    if fast:
        dense_shapes = [(64, 16), (40, 16)]
    for C, bc in dense_shapes:
        lens = _boundary_lengths(C, bc)
        out += check_dense_index_map(C, bc, lens)
        cases += 1

    # paged: real ledger states + synthetic -1 tails
    bs = 16
    kv = _ledger_states(bs)
    out += check_paged_index_map(kv._tables, kv.lengths(),
                                 kv.pool.num_blocks, bs,
                                 where=f"paged(ledger, bs={bs})")
    cases += 1
    # synthetic: every row unallocated (all -1) at length 0 — the state
    # right after a mass free; only the scratch clamp keeps it in range
    empty = np.full((3, 4), -1, np.int32)
    out += check_paged_index_map(empty, [0, 0, 0], 8, bs,
                                 where="paged(all-unallocated)")
    cases += 1

    # flash: GQA folds including H == Kv (MHA) and single-group
    flash_shapes = [(2, 8, 2, 4, 4), (1, 4, 4, 2, 2), (3, 6, 1, 4, 1)]
    if fast:
        flash_shapes = flash_shapes[:2]
    for B, H, Kv, n_q, n_k in flash_shapes:
        out += check_flash_index_map(B, H, Kv, n_q, n_k)
        cases += 1

    if log is not None:
        log(f"kernelcheck: {cases} cases, {len(out)} violations")
    return out, {"kernel_cases": cases, "fast": fast}


def check_flash_index_map(B: int, H: int, Kv: int, n_q: int,
                          n_k: int) -> List[Violation]:
    """Evaluate the flash K/V index_map over the (B*H, n_q, n_k) grid:
    the GQA fold must stay inside the flattened [B*Kv] KV operand, pick
    the right (batch, kv-head) row, and be constant across the g query
    heads of one group."""
    from repro.kernels.flash_attention.kernel import flash_kv_index_map
    out: List[Violation] = []
    g = H // Kv
    kv_index = flash_kv_index_map(H, Kv)
    where = f"flash(B={B}, H={H}, Kv={Kv})"
    for bh in range(B * H):
        for qi in range(n_q):
            for ki in range(n_k):
                row, kblk, di = _ints(kv_index(bh, qi, ki))
                if not (kblk == ki and di == 0):
                    out.append(Violation(
                        PASS, "flash-block-identity", where,
                        f"grid ({bh},{qi},{ki}) mapped k-block "
                        f"({kblk},{di}), expected ({ki},0)"))
                if not 0 <= row < B * Kv:
                    out.append(Violation(
                        PASS, "flash-row-range", where,
                        f"grid ({bh},{qi},{ki}) emits KV row {row} "
                        f"outside [0, {B * Kv})"))
                    continue
                want = (bh // H) * Kv + (bh % H) // g
                if row != want:
                    out.append(Violation(
                        PASS, "flash-gqa-fold", where,
                        f"program {bh} (batch {bh // H}, head {bh % H}) "
                        f"read KV row {row}, expected {want}"))
    return out
