"""On-device microbenchmarks for the three alpha-beta primitives.

Each runner times a jit-compiled primitive over a workload sweep and emits
``(x, t)`` samples in EXACTLY the units ``repro.core.perf_model`` fits
(module header there):

  * GEMM      x = m * k * n            (product of the three GEMM dims)
  * attention y = N_h * B * S^2 * (d_k + d_v)
  * comm      z = bytes on the wire per device (a2e/e2a path)

``run_microbenchmarks`` bundles the three sweeps into the ``measured``
dict ``fit_profile`` / ``calibrated_stage_models`` consume;
``calibrate`` goes one step further and returns the fitted
``HardwareProfile`` plus per-primitive R^2 (the paper reports
R^2 > 0.994 on its GPUs — Fig. 7).

The all_to_all runner needs a live mesh whose expert axis spans > 1
device; without one (single-device CPU hosts, unit tests) it falls back
to a bytes-proportional on-device copy proxy and marks the result
``proxy=True`` so stores/reports can flag that the comm fit is not a
wire measurement.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.perf_model import (HardwareProfile, fit_alpha_beta,
                                   fit_profile)

# (m, k, n) GEMM sweeps: products span ~3 decades so the intercept
# (launch overhead) and slope (per-unit time) are both identifiable.
GEMM_SWEEP: Tuple[Tuple[int, int, int], ...] = (
    (128, 256, 256), (256, 512, 512), (512, 512, 1024), (512, 1024, 1024),
    (1024, 1024, 1024), (1024, 2048, 1024), (2048, 2048, 1024),
)
GEMM_SWEEP_FAST: Tuple[Tuple[int, int, int], ...] = (
    (128, 256, 256), (256, 256, 512), (256, 512, 512), (512, 512, 512),
    (512, 1024, 512), (512, 1024, 1024), (1024, 1024, 1024),
)

# (B, S, N_h, d) attention sweeps (d_k = d_v = d).
ATTN_SWEEP: Tuple[Tuple[int, int, int, int], ...] = (
    (1, 128, 4, 64), (1, 256, 4, 64), (2, 256, 4, 64), (2, 512, 4, 64),
    (4, 512, 4, 64), (4, 512, 8, 64),
)
ATTN_SWEEP_FAST: Tuple[Tuple[int, int, int, int], ...] = (
    (1, 64, 4, 64), (1, 128, 4, 64), (2, 128, 4, 64), (2, 256, 4, 64),
    (4, 256, 4, 64),
)

# per-device payload sizes (bytes) for the comm sweep
COMM_SWEEP_BYTES: Tuple[int, ...] = tuple(2 ** i for i in range(16, 26))
COMM_SWEEP_BYTES_FAST: Tuple[int, ...] = tuple(2 ** i for i in range(20, 26))

# (B, C, fill) single-query ragged decode sweeps at fixed (Kv, D): B rows
# each attending fill*C cached positions. The unit is BYTES STREAMED
# (sum(lengths) * Kv * 2D * itemsize) — decode attention is
# bandwidth-bound, so its alpha-beta lives on a different line than the
# compute-bound prefill attention fit.
DECODE_SWEEP: Tuple[Tuple[int, int, float], ...] = (
    (1, 256, 1.0), (2, 256, 0.5), (2, 512, 1.0), (4, 512, 0.5),
    (4, 1024, 1.0), (8, 1024, 0.75), (8, 2048, 0.5),
)
DECODE_SWEEP_FAST: Tuple[Tuple[int, int, float], ...] = (
    (1, 128, 1.0), (2, 128, 0.5), (2, 256, 1.0), (4, 256, 0.5),
    (4, 512, 0.5),
)
DECODE_KV_HEADS = 4
DECODE_HEAD_DIM = 64


def time_fn(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall-time per call of a jit-compiled ``fn`` (blocks on the
    result, so device async dispatch does not leak into the sample; the
    median discards scheduler hiccups that would poison a mean on shared
    CI hosts)."""
    import jax
    for _ in range(max(warmup, 0)):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    n = len(ts)
    return ts[n // 2] if n % 2 else 0.5 * (ts[n // 2 - 1] + ts[n // 2])


@dataclass
class MicrobenchSamples:
    """One primitive's measured sweep: ``xs`` in perf_model units, ``ts``
    in seconds. ``proxy`` flags a stand-in measurement (e.g. the comm
    sweep on a single-device host)."""

    kind: str
    xs: List[float] = field(default_factory=list)
    ts: List[float] = field(default_factory=list)
    proxy: bool = False

    def as_xt(self) -> Tuple[List[float], List[float]]:
        return self.xs, self.ts


def measure_gemm(shapes: Optional[Sequence[Tuple[int, int, int]]] = None,
                 dtype=None, warmup: int = 2, iters: int = 5
                 ) -> MicrobenchSamples:
    """x = m*k*n for a [m,k] @ [k,n] matmul."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    shapes = GEMM_SWEEP if shapes is None else shapes
    out = MicrobenchSamples("gemm")
    f = jax.jit(lambda a, b: a @ b)
    key = jax.random.PRNGKey(0)
    for m, k, n in shapes:
        a = jax.random.normal(key, (m, k), dtype)
        b = jax.random.normal(key, (k, n), dtype)
        out.xs.append(float(m * k * n))
        out.ts.append(time_fn(f, a, b, warmup=warmup, iters=iters))
    return out


def measure_attention(shapes: Optional[Sequence[Tuple[int, int, int, int]]]
                      = None, dtype=None, warmup: int = 2, iters: int = 5
                      ) -> MicrobenchSamples:
    """y = N_h * B * S^2 * (d_k + d_v) for causal SDPA."""
    import jax
    import jax.numpy as jnp
    from repro.models.attention import _causal_mask, _sdpa
    dtype = dtype or jnp.float32
    shapes = ATTN_SWEEP if shapes is None else shapes
    out = MicrobenchSamples("attn")
    key = jax.random.PRNGKey(0)
    f = jax.jit(lambda q, k, v, m: _sdpa(q, k, v, m))
    for B, S, H, D in shapes:
        q = jax.random.normal(key, (B, S, H, D), dtype)
        k = jax.random.normal(key, (B, S, H, D), dtype)
        v = jax.random.normal(key, (B, S, H, D), dtype)
        mask = _causal_mask(jnp.arange(S), jnp.arange(S), None)
        out.xs.append(float(H * B * S * S * (D + D)))
        out.ts.append(time_fn(f, q, k, v, mask, warmup=warmup, iters=iters))
    return out


def measure_all_to_all(mesh=None, axis: str = "model",
                       sizes_bytes: Optional[Sequence[int]] = None,
                       dtype=None, warmup: int = 2, iters: int = 5
                       ) -> MicrobenchSamples:
    """z = bytes per device moved by one tiled all_to_all on ``mesh``'s
    ``axis`` — the live-wire a2e/e2a measurement. Falls back to an
    on-device copy proxy (``proxy=True``) when the axis spans one device.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    dtype = dtype or jnp.float32
    sizes = COMM_SWEEP_BYTES if sizes_bytes is None else sizes_bytes
    itemsize = jnp.dtype(dtype).itemsize
    mo = mesh.shape[axis] if (mesh is not None and axis in mesh.shape) else 1

    out = MicrobenchSamples("comm", proxy=mo <= 1)
    key = jax.random.PRNGKey(0)
    for z in sizes:
        elems = max(int(z) // itemsize, mo * mo)
        if mo > 1:
            # local [mo, c]: all_to_all exchanges the full local buffer
            # (z bytes per device) across the expert axis, like one a2e
            # chunk of Eq. 4
            c = max(elems // mo, 1)
            x = jax.random.normal(key, (mo * mo, c), dtype)

            def a2a(xl):
                return jax.lax.all_to_all(xl, axis, split_axis=0,
                                          concat_axis=1, tiled=True)

            f = jax.jit(jax.shard_map(a2a, mesh=mesh, in_specs=P(axis),
                                      out_specs=P(axis)))
            z_dev = float(mo * c * itemsize)
        else:
            # proxy: a bytes-proportional on-device copy. Keeps the fit
            # machinery exercised on hosts with no multi-device axis; the
            # resulting beta is HBM-ish, NOT a wire bandwidth.
            x = jax.random.normal(key, (elems,), dtype)
            f = jax.jit(lambda a: a + 1)
            z_dev = float(elems * itemsize)
        out.xs.append(z_dev)
        out.ts.append(time_fn(f, x, warmup=warmup, iters=iters))
    return out


def measure_decode_attention(shapes: Optional[Sequence[Tuple[int, int, float]]]
                             = None, dtype=None, warmup: int = 2,
                             iters: int = 5) -> MicrobenchSamples:
    """z = sum(lengths) * Kv * (d_k + d_v) * itemsize — the KV bytes one
    ragged decode step streams. Times the Pallas kernel on TPU; on other
    hosts the jnp reference stands in (``proxy=True``) because interpret
    mode measures the interpreter, not the memory system."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import on_tpu
    from repro.kernels.decode_attention import ops as dec_ops
    from repro.kernels.decode_attention.ref import decode_attention_ref
    dtype = dtype or jnp.float32
    shapes = DECODE_SWEEP if shapes is None else shapes
    kv, d = DECODE_KV_HEADS, DECODE_HEAD_DIM
    itemsize = jnp.dtype(dtype).itemsize
    use_kernel = on_tpu()
    out = MicrobenchSamples("decode", proxy=not use_kernel)
    key = jax.random.PRNGKey(0)
    if use_kernel:
        f = jax.jit(lambda q, k, v_, l: dec_ops.decode_attention(q, k, v_, l))
    else:
        f = jax.jit(decode_attention_ref)
    for B, C, fill in shapes:
        # size the cache to the occupied length (rather than masking a
        # full-C cache): the jnp reference computes all C positions and
        # masks, which would decouple its time from the bytes unit; the
        # kernel skips past-length blocks anyway, so both paths stream
        # exactly the bytes the sample claims
        c_eff = max(int(C * fill), 16)
        q = jax.random.normal(key, (B, kv, d), dtype)
        k = jax.random.normal(key, (B, kv, c_eff, d), dtype)
        v = jax.random.normal(key, (B, kv, c_eff, d), dtype)
        lens = jnp.full((B,), c_eff, jnp.int32)
        out.xs.append(float(B * c_eff * kv * 2 * d * itemsize))
        out.ts.append(time_fn(f, q, k, v, lens, warmup=warmup, iters=iters))
    return out


def _measure_kind(kind: str, fast: bool, mesh, axis: str, dtype,
                  warmup: int, iters: int) -> MicrobenchSamples:
    if kind == "gemm":
        return measure_gemm(GEMM_SWEEP_FAST if fast else GEMM_SWEEP,
                            dtype=dtype, warmup=warmup, iters=iters)
    if kind == "attn":
        return measure_attention(ATTN_SWEEP_FAST if fast else ATTN_SWEEP,
                                 dtype=dtype, warmup=warmup, iters=iters)
    if kind == "comm":
        # comm samples are the cheapest to take and (on the copy proxy)
        # the most scheduler-noise-prone — buy stability with extra iters
        return measure_all_to_all(mesh, axis,
                                  COMM_SWEEP_BYTES_FAST if fast
                                  else COMM_SWEEP_BYTES,
                                  dtype=dtype, warmup=warmup,
                                  iters=max(3 * iters, 15))
    if kind == "decode":
        return measure_decode_attention(
            DECODE_SWEEP_FAST if fast else DECODE_SWEEP,
            dtype=dtype, warmup=warmup, iters=iters)
    raise ValueError(f"unknown microbench kind {kind!r}")


#: the full primitive set ``calibrate`` sweeps (decode rides along as the
#: optional fourth alpha-beta — ``fit_profile`` treats it as such)
MICROBENCH_KINDS = ("gemm", "attn", "comm", "decode")


def run_microbenchmarks(fast: bool = False, mesh=None, axis: str = "model",
                        dtype=None, warmup: Optional[int] = None,
                        iters: Optional[int] = None,
                        kinds: Tuple[str, ...] = MICROBENCH_KINDS
                        ) -> Dict[str, MicrobenchSamples]:
    """The full sweep set, keyed by primitive — ``{k: v.as_xt() ...}`` is
    exactly the ``measured`` dict ``calibrated_stage_models`` expects."""
    warmup = (1 if fast else 2) if warmup is None else warmup
    iters = (5 if fast else 9) if iters is None else iters
    return {kind: _measure_kind(kind, fast, mesh, axis, dtype, warmup,
                                iters)
            for kind in kinds}


@dataclass
class CalibrationResult:
    profile: HardwareProfile
    fit_r2: Dict[str, float]             # per primitive
    samples: Dict[str, MicrobenchSamples]
    wall_s: float

    @property
    def comm_is_proxy(self) -> bool:
        return self.samples["comm"].proxy

    def min_r2(self) -> float:
        return min(self.fit_r2.values())


def calibrate(name: str = "calibrated", fast: bool = False, mesh=None,
              axis: str = "model", dtype=None, min_r2: float = 0.9,
              max_retries: int = 2, warmup: Optional[int] = None,
              iters: Optional[int] = None) -> CalibrationResult:
    """Measure -> fit: the paper's offline phase on THIS host. Returns the
    fitted profile + the R^2 quality of each primitive fit.

    A primitive whose fit lands below ``min_r2`` (scheduler noise hit the
    sweep — a transient, not a property of the hardware) is re-measured up
    to ``max_retries`` times, keeping the best-R^2 sweep. ``min_r2=0``
    disables retries."""
    t0 = time.perf_counter()
    warmup_ = (1 if fast else 2) if warmup is None else warmup
    iters_ = (5 if fast else 9) if iters is None else iters
    samples = run_microbenchmarks(fast=fast, mesh=mesh, axis=axis,
                                  dtype=dtype, warmup=warmup_, iters=iters_)
    profile, r2s = fit_profile({k: v.as_xt() for k, v in samples.items()},
                               name=name)
    for _ in range(max_retries):
        bad = [k for k, v in r2s.items() if v < min_r2]
        if not bad:
            break
        for kind in bad:
            retaken = _measure_kind(kind, fast, mesh, axis, dtype,
                                    warmup_, iters_)
            _, r2_new = fit_alpha_beta(*retaken.as_xt())
            if r2_new > r2s[kind]:
                samples[kind] = retaken
        profile, r2s = fit_profile(
            {k: v.as_xt() for k, v in samples.items()}, name=name)
    return CalibrationResult(profile=profile, fit_r2=r2s, samples=samples,
                             wall_s=time.perf_counter() - t0)
