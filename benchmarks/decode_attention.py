"""Ragged decode-attention sweep: occupancy fraction x block size.

Interpret-mode on CPU, so wall times are correctness-path cost only — NOT
a TPU timing. The structural quantity that matters for the TPU target is
the executed-KV-block count per row, which the kernel itself reports:
streamed bytes scale with ceil(length/bc) blocks, not C/bc, so the
derived column tracks the fraction of the dense cache stream a given
occupancy actually pays.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import csv_row
from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref


def run():
    rows = []
    key = jax.random.PRNGKey(0)
    B, H, Kv, D, C = 4, 8, 2, 64, 2048
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Kv, C, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Kv, C, D), jnp.float32)

    proportional = True
    for frac in (0.125, 0.5, 1.0):
        lengths = jnp.full((B,), int(C * frac), jnp.int32)
        for bc in (256, 512):
            fn = jax.jit(lambda q, k, v, ln, bc=bc: decode_attention_pallas(
                q, k, v, ln, bc=bc, return_block_counts=True))
            out, counts = fn(q, k, v, lengths)       # compile + warm
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            out, counts = fn(q, k, v, lengths)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) * 1e6
            executed = int(jnp.max(counts))
            total = C // bc
            expect = -(-int(C * frac) // bc)         # ceil(len/bc)
            proportional &= executed == expect
            rows.append(csv_row(
                f"decode_attention.occ{frac}_bc{bc}", dt,
                f"blocks_per_row={executed}/{total};expect={expect};"
                f"stream_frac={executed / total:.3f}"))
    # oracle cost at full cache, for scale
    t0 = time.perf_counter()
    jax.block_until_ready(decode_attention_ref(
        q, k, v, jnp.full((B,), C, jnp.int32)))
    rows.append(csv_row("decode_attention.ref_dense",
                        (time.perf_counter() - t0) * 1e6,
                        f"streams_full_cache=C/{C}"))
    return rows, {"block_skip_proportional": proportional}


if __name__ == "__main__":
    for r in run()[0]:
        print(r)
