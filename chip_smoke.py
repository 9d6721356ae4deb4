#!/usr/bin/env python3
"""Bring-up check on a TPU: serve qwen2-moe-a2.7b at its published widths
through ``ServingEngine``.

    python3 chip_smoke.py             # one chip: 6 of 24 layers, bf16
    python3 chip_smoke.py --chips 4   # four chips: DEP on a (1, 4) mesh,
                                      # all 24 layers, vs capacity MoE

One process drives everything: a chip belongs to one process at a time.
Weights are random from ``--seed``. The last line of standard output is
``{"ok": true, "device": {...}}`` only when every check passed on a TPU;
without a TPU, or without the repository beside this file, the script
exits non-zero and prints no such line. The times it prints are one run's
wall-clock, compilation included where said, and are not benchmark
figures.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# One chip, 16 GB. The published model is 24 layers of ~570 M parameters
# (60 routed experts x 3 x 2048 x 1408, a 4 x 1408-wide shared FFN,
# attention) = 1.14 GB each in bf16, plus 1.25 GB of untied embedding and
# head. The decode step compiled for a v5e chip at 6 layers, 16 slots and
# 2048 positions (compiled.memory_analysis()) takes 9.70 GB of arguments
# (8.09 GB of weights, 1.61 GB of K/V cache), 1.62 GB of outputs (the
# updated cache) and 0.15 GB of temporaries: 11.5 GB. A seventh layer
# would add 1.14 GB of weights and 0.54 GB of cache in and out (13.2 GB)
# and leave too little for the prefill of the 1100-token prompt, whose
# no-drop expert buffers take about 2 GB; eight layers need 14.9 GB.
ONE_CHIP_LAYERS = 6
ONE_CHIP_SLOTS = 16
ONE_CHIP_CONTEXT = 2048
# prompt lengths: one past 1000 tokens, the rest mixed down to a single
# token (which takes the no-prefill path); even requests greedy, odd ones
# sampled
ONE_CHIP_PROMPTS = (1100, 520, 130, 60, 33, 17, 7, 1)
ONE_CHIP_NEW = (8, 16, 24, 24, 32, 32, 32, 32)

FOUR_CHIP_SLOTS = 4
FOUR_CHIP_CONTEXT = 512
# every prompt fills its prefill bucket (256 tokens prefilled, the 257th
# fed through decode), so the logit at the bucket end is a real token's
FOUR_CHIP_PROMPT = 257
FOUR_CHIP_NEW = 4

# Tolerances, on the relative L2 error of one row's logits,
# ||a - b|| / ||b||: the median over the rows, and every row.
#
# bf16 serving vs the float32 reference. bf16 keeps 8 significant bits, a
# relative rounding of 2^-9 per value, which the 2048-wide sums and the
# residual layers carry to a few percent of the logits. Rounding also
# flips a router's top-k where two experts score nearly alike, and that
# token's layer output then moves by a share of an expert's output: such
# rows stand out from the rest. The same check on the CPU at a reduced
# width (d_model 512, 60 experts of 352, 4 and 6 layers, 7 prompts of 1 to
# 300 tokens, 16 seeds) gave bf16 row medians of 0.018 to 0.043 and single
# rows up to 0.33; weights rounded to fp8 (e4m3, 2^-4 relative rounding)
# gave medians of 0.24 to 0.29, and e5m2 0.42 to 0.45. On one v5e chip at
# this script's sizes (seed 0) seven rows gave 0.009 to 0.14 and the
# 1100-token prompt 0.57: its last token's layer-0 router scored its 4th
# and 5th experts 0.0036 apart in the reference, and the same row served
# through dense SDPA instead of the kernel differs from the kernel's by
# only 0.016, so that row is a flip and not a kernel fault. A row of zeros
# has an error of 1.0, a row unrelated to the reference (a wrong cache row
# or position) one near sqrt(2) = 1.41: the logits of other prompts gave
# 1.32 to 1.45 (four CPU devices, d_model 512, 24 layers).
# So: median <= 0.1 admits bf16 and fails fp8; every row <= 0.9 admits a
# routing flip and fails a zero or unrelated row.
REF_MEDIAN_TOL = 0.1
# DEP vs capacity MoE on the same mesh, both bf16, same weights and tokens:
# the same math, with the expert combine summed in another order (a psum
# over the expert axis against one gather) and the expert GEMMs run on
# other buffer shapes, so rows differ by bf16 rounding, which 24 layers
# amplify, plus routing flips. On four CPU devices at d_model 512 rows
# differed by 0.007 to 0.010 at 4 layers and at 24 layers by 0.023 to 0.31
# (medians 0.088 to 0.107); in float32 the same 24-layer
# comparison agrees to 2e-6, so the gap is rounding, not logic. A wrong
# dispatch or combine scrambles every row (error of order 1).
DEP_MEDIAN_TOL = 0.25
ROW_TOL = 0.9


class SmokeFailure(RuntimeError):
    pass


def _device_json(devices):
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def _qwen2_moe(num_layers: int):
    """qwen2-moe-a2.7b at every published width, cut only in depth, with
    an expert capacity that never drops a token: each expert's buffer can
    hold every token (capacity factor E / top_k), so the served layer
    computes the same function as the exact reference. The default
    capacity factor of 1.25 drops overflow tokens, which gives a
    different result and not a rounding error."""
    from repro.configs.qwen2_moe_a2_7b import CONFIG
    m = CONFIG.moe
    return dataclasses.replace(
        CONFIG, num_layers=num_layers,
        moe=dataclasses.replace(m, capacity_factor=m.num_experts / m.top_k))


def _policy(cfg, device):
    """FinDEP planning from the registry profile of ``device``'s kind (an
    unknown kind raises). Plans target the four-chip v5e host, two
    attention-role and two expert-role chips; on one chip they set the
    prefill chunking."""
    from repro.configs.base import DepClusterConfig
    from repro.core.perf_model import profile_for_device
    from repro.core.planner import FinDEPPlanner
    from repro.sched import FinDEPPolicy
    hw = profile_for_device(device)
    print(f"planning from profile {hw.name!r} for {device.device_kind!r}")
    return FinDEPPolicy(FinDEPPlanner(cfg, DepClusterConfig(4, 2, 2), hw))


def _weight_bytes(params, device) -> int:
    import jax
    return sum(s.data.nbytes for leaf in jax.tree.leaves(params)
               for s in leaf.addressable_shards if s.device == device)


def _requests(cfg, lengths, new, seed: int, sample_odd: bool = True):
    import numpy as np
    from repro.runtime import Request
    rng = np.random.RandomState(seed)
    reqs = []
    for i, (n, m) in enumerate(zip(lengths, new)):
        sampled = sample_odd and i % 2 == 1
        reqs.append(Request(
            prompt=rng.randint(0, cfg.vocab_size, size=n).tolist(),
            max_new_tokens=m, temperature=0.8 if sampled else 0.0,
            top_k=40 if sampled else 0))
    return reqs


def check_served(reqs) -> None:
    from repro.runtime import RequestState
    bad = [(r.request_id, r.state.value, r.error, len(r.output))
           for r in reqs
           if r.state != RequestState.FINISHED or r.error is not None
           or len(r.output) != r.max_new_tokens]
    if bad:
        raise SmokeFailure(f"requests not served in full: {bad}")


def check_kernel_in_hlo(hlo_text: str) -> None:
    if "tpu_custom_call" not in hlo_text:
        raise SmokeFailure("the compiled decode step holds no Pallas "
                           "kernel (no tpu_custom_call)")


def decode_step_hlo(eng):
    """Compile the engine's decode step for its current state and return
    (hlo text, memory analysis). The persistent compilation cache, when
    warm from the served steps, makes this a lookup."""
    import jax
    import jax.numpy as jnp
    lengths = jnp.asarray(eng.kv.lengths(), jnp.int32)
    tables = eng.kv.table_array() if eng._paged else None
    compiled = eng._decode_jit.lower(
        eng.params, eng.last_tokens, eng.kv.caches, eng.temps, eng.top_ks,
        jax.random.PRNGKey(0), lengths, tables, plan=None, use_topk=False,
        placement=None, cap_scale=1.0, collect_stats=False).compile()
    return compiled.as_text(), compiled.memory_analysis()


def compare_rows(name: str, got, want, median_tol: float,
                 row_tol: float = ROW_TOL) -> float:
    """Relative L2 error of each row; fails unless the median is within
    ``median_tol`` and every row within ``row_tol``. Returns the median."""
    import numpy as np
    from repro.models.reference import relative_error
    errs = [relative_error(g, w) for g, w in zip(got, want)]
    med, worst = float(np.median(errs)), max(errs)
    print(f"{name}: relative L2 error per row "
          f"{[float(f'{e:.3g}') for e in errs]}, median {med:.3g} "
          f"(tolerance {median_tol}), worst {worst:.3g} "
          f"(tolerance {row_tol})")
    if not (med <= median_tol and worst <= row_tol):
        raise SmokeFailure(f"{name}: median {med:.3g} or worst row "
                           f"{worst:.3g} out of tolerance")
    return med


def one_chip_phase(device, cfg=None, *, num_slots=ONE_CHIP_SLOTS,
                   max_context=ONE_CHIP_CONTEXT, prompts=ONE_CHIP_PROMPTS,
                   new=ONE_CHIP_NEW, seed: int = 0,
                   tol: float = REF_MEDIAN_TOL):
    """Serve mixed requests on one device through ServingEngine in bf16,
    then hold the first decode step's logits against the float32
    reference forward of each prompt."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.reference import reference_logits
    from repro.runtime import ServingEngine

    if cfg is None:
        cfg = _qwen2_moe(ONE_CHIP_LAYERS)
    print(f"model {cfg.name}: {cfg.num_layers} of 24 layers (depth cut "
          f"only), d_model {cfg.d_model}, {cfg.num_heads} heads x "
          f"{cfg.head_dim}, {cfg.moe.num_experts} experts x "
          f"{cfg.moe.expert_ffn_dim} top-{cfg.moe.top_k}, "
          f"{cfg.moe.num_shared_experts} shared, vocab {cfg.vocab_size}")
    print("dtype bfloat16 (weights, activations, KV cache); reference "
          "float32 at matmul precision 'highest'")
    t = time.perf_counter()
    eng = ServingEngine(cfg, num_slots=num_slots, max_context=max_context,
                        plan_policy=_policy(cfg, device),
                        dtype=jnp.bfloat16, seed=seed)
    jax.block_until_ready(eng.params)
    print(f"weights: {_weight_bytes(eng.params, device)} bytes on "
          f"{device} (init {time.perf_counter() - t:.1f} s wall)")
    print(f"decode implementation: {eng.model.ctx.attn_impl} "
          "(ragged Pallas kernel)")

    reqs = _requests(cfg, prompts, new, seed)
    for r in reqs:
        eng.submit(r)
    t = time.perf_counter()
    eng.step()
    first = np.asarray(eng.last_logits)
    t_first = time.perf_counter() - t
    slot_of = {r.request_id: i for i, r in enumerate(eng.slots)
               if r is not None}
    if len(slot_of) != len(reqs):
        raise SmokeFailure(f"{len(slot_of)} of {len(reqs)} requests "
                           "decoded in the first step")
    steps0 = eng.stats.steps
    t = time.perf_counter()
    eng.run()
    t_steady = time.perf_counter() - t
    check_served(reqs)
    n_tok = sum(len(r.output) for r in reqs)
    print(f"served {len(reqs)} requests ({sum(prompts)} prompt tokens, "
          f"{n_tok} generated tokens) with no error")
    print(f"one run's wall times: first step {t_first:.1f} s (admission, "
          f"prefill and decode, compiles included); "
          f"{eng.stats.steps - steps0} further decode steps "
          f"{t_steady:.2f} s")

    hlo, mem = decode_step_hlo(eng)
    check_kernel_in_hlo(hlo)
    print("compiled decode step holds the Pallas decode kernel "
          "(tpu_custom_call)")
    if mem is not None:
        print(f"decode step memory analysis: arguments "
              f"{mem.argument_size_in_bytes}, outputs "
              f"{mem.output_size_in_bytes}, temporaries "
              f"{mem.temp_size_in_bytes} bytes")
    stats = device.memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} on {device}")

    eng.kv.caches = None             # room for the reference's temporaries
    want = [np.asarray(reference_logits(eng.params, cfg, r.prompt,
                                        last_only=True))[0] for r in reqs]
    got = [first[slot_of[r.request_id]] for r in reqs]
    return compare_rows("first decode step vs float32 reference", got,
                        want, tol)


def four_chip_phase(devices, cfg=None, *, num_slots=FOUR_CHIP_SLOTS,
                    max_context=FOUR_CHIP_CONTEXT, prompt=FOUR_CHIP_PROMPT,
                    new=FOUR_CHIP_NEW, seed: int = 0,
                    tol: float = DEP_MEDIAN_TOL):
    """ServingEngine with DEP MoE over a (1, n) ("data", "model") mesh at
    all 24 layers; its prefill and first decode-step logits against the
    same weights and tokens through capacity MoE on the same mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.models.transformer import ExecutionContext
    from repro.runtime import ServingEngine

    n = len(devices)
    if cfg is None:
        cfg = _qwen2_moe(24)
    mesh = make_mesh((1, n), ("data", "model"), devices=devices)
    print(f"model {cfg.name}: all {cfg.num_layers} layers at published "
          f"widths, bf16, DEP over {n} devices, mesh {dict(mesh.shape)}")
    t = time.perf_counter()
    eng = ServingEngine(cfg, mesh=mesh, num_slots=num_slots,
                        max_context=max_context,
                        plan_policy=_policy(cfg, devices[0]),
                        dtype=jnp.bfloat16, seed=seed)
    jax.block_until_ready(eng.params)
    print(f"engine moe_impl={eng.model.ctx.moe_impl}, decode "
          f"{eng.model.ctx.attn_impl} (init "
          f"{time.perf_counter() - t:.1f} s wall)")
    for name, a in eng.params["layers"][0]["moe"]["experts"].items():
        print(f"layer 0 experts.{name} {a.shape}: {a.sharding.spec} over "
              f"{len(a.sharding.device_set)} devices, shard "
              f"{a.addressable_shards[0].data.shape}")
    for i, layer in enumerate(eng.params["layers"]):
        for name, a in layer["moe"]["experts"].items():
            if (len(a.sharding.device_set) != n
                    or a.addressable_shards[0].data.shape[0]
                    != a.shape[0] // n):
                raise SmokeFailure(f"layer {i} experts.{name} is not "
                                   f"split over {n} devices")
    for d in devices:
        print(f"weights on {d}: {_weight_bytes(eng.params, d)} bytes")

    reqs = _requests(cfg, [prompt] * num_slots, [new] * num_slots, seed,
                     sample_odd=False)
    for r in reqs:
        eng.submit(r)
    t = time.perf_counter()
    eng.step()
    dep_dec = np.asarray(eng.last_logits)
    slots, dep_pre = eng.last_prefill_logits
    dep_pre = np.asarray(dep_pre)
    rows = [eng.slots[s] for s in slots]     # the request in each row
    print(f"first step {time.perf_counter() - t:.1f} s wall (prefill of "
          f"{len(slots)} x {prompt - 1} tokens and one decode step, "
          "compiles included)")
    eng.run()
    check_served(reqs)
    print(f"served {len(reqs)} requests, "
          f"{sum(len(r.output) for r in reqs)} tokens, with no error")
    for d in devices:
        print(f"peak_bytes_in_use {(d.memory_stats() or {}).get('peak_bytes_in_use')} on {d}")

    # the same weights and tokens through capacity MoE on the same mesh,
    # op by op (the 24 layers share their compiled ops, where one jit of
    # the prefill and the decode step would compile all 24 again)
    cap = build_model(cfg, ctx=ExecutionContext(
        mesh=mesh, attn_impl="decode_kernel", moe_impl="capacity"),
        dtype=jnp.bfloat16)
    toks = jnp.asarray([r.prompt[:prompt - 1] for r in rows], jnp.int32)
    cap_pre, caches = cap.prefill(eng.params, toks, seq_budget=max_context)
    last = jnp.asarray([[r.prompt[-1]] for r in rows], jnp.int32)
    cap_dec, _ = cap.decode_step(
        eng.params, last, caches,
        lengths=jnp.full((len(rows),), prompt, jnp.int32))
    meds, failed = [], []
    for name, got, want in (
            ("DEP vs capacity, prefill logits", dep_pre, cap_pre[:, 0]),
            ("DEP vs capacity, first decode step logits",
             [dep_dec[s] for s in slots], cap_dec[:, 0])):
        try:                          # print both before failing on either
            meds.append(compare_rows(name, got, np.asarray(want), tol))
        except SmokeFailure as e:
            failed.append(str(e))
    if failed:
        raise SmokeFailure("; ".join(failed))
    return max(meds)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve on one chip and check against the "
                         "float32 reference; 4: the DEP path across four "
                         "chips against capacity MoE, and nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this "
              f"file ({e})", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()         # before the first compile
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX finds "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    devices = devices[:args.chips]
    print(f"devices: {len(devices)} x {devices[0].device_kind}; "
          f"compilation cache {cache}")
    try:
        if args.chips == 1:
            one_chip_phase(devices[0], seed=args.seed)
        else:
            four_chip_phase(devices, seed=args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": _device_json(devices)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
