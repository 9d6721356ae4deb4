"""End-to-end serving driver (the paper's workload): batched requests
against an MoE model through the continuous-batching engine with a
pluggable scheduling policy (the paper's FinDEP online planner by default,
or any baseline schedule via --policy) and a pluggable admission policy
(--admission fcfs|spf|token_budget, --token-budget N for Sarathi-style
chunked prefill admission).

The planner's cost models come from repro.profiling's measured-cost loop:

  --calibrate          run the on-device microbenchmarks now, fit the
                       alpha-beta models, persist them to --profile-store
                       (named --profile NAME, default: the host key slug)
  --profile NAME       plan from a previously stored fit (or a registry
                       profile: paper_a6000 / tpu_v5e) — no re-measurement
  --drift-threshold X  enable drift detection: a cached plan whose EWMA
                       predicted-vs-measured residual exceeds X is
                       re-solved in the background while the stale plan
                       keeps serving

and the expert placement loop (repro.placement) closes observe -> place
-> plan over the gate's routing skew:

  --replicate-hot-k K      replicate the K hottest experts onto every EP
                           rank when a re-balance lands (REP task: their
                           tokens never cross the A2E/E2A wire)
  --rebalance-threshold X  re-solve the expert->rank map in the
                           background when the worst rank's observed load
                           exceeds X times the uniform share (e.g. 1.25)

Observability (repro.obs):

  --trace-out OUT.json       record phase + request-lifecycle spans and
                             write a Perfetto-loadable Chrome trace
  --metrics-out OUT.jsonl    append metrics-registry snapshots (final,
                             or every --metrics-interval seconds); the
                             final TTFT/TPOT p50/p99 summary prints from
                             the same registry's histograms

Run:  PYTHONPATH=src python examples/serve_moe.py [--requests 16]
      PYTHONPATH=src python examples/serve_moe.py --policy sequential
      PYTHONPATH=src python examples/serve_moe.py --calibrate
      PYTHONPATH=src python examples/serve_moe.py \
          --profile $(ls .repro-profiles | head -1 | sed s/.json//)
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.configs.base import DepClusterConfig
from repro.core import FinDEPPlanner, PAPER_A6000
from repro.core.perf_model import profile_for_device
from repro.core.planner import PlannerConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.profiling import ProfileStore
from repro.runtime import ADMISSIONS, Request, ServingEngine
from repro.sched import POLICIES, make_policy


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--arch", default="qwen2-moe-a2.7b")
    ap.add_argument("--policy", choices=POLICIES, default="findep",
                    help="scheduling policy for the MoE layers")
    ap.add_argument("--admission", choices=ADMISSIONS, default="fcfs",
                    help="request admission policy")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="per-step prefill token budget (chunked prefill)")
    ap.add_argument("--calibrate", action="store_true",
                    help="microbenchmark this host, fit + persist a "
                         "HardwareProfile, and plan from it")
    ap.add_argument("--profile", default=None,
                    help="plan from a stored/registry profile by name "
                         "(with --calibrate: the name to store under)")
    ap.add_argument("--profile-store", default=".repro-profiles",
                    help="ProfileStore root directory")
    ap.add_argument("--drift-threshold", type=float, default=None,
                    help="enable drift-triggered background plan refresh "
                         "at this |residual| (e.g. 0.5)")
    ap.add_argument("--replicate-hot-k", type=int, default=0,
                    help="replicate the K hottest experts onto every EP "
                         "rank at each re-balance (0 = no replication)")
    ap.add_argument("--rebalance-threshold", type=float, default=None,
                    help="background expert re-placement when the worst "
                         "rank's load exceeds this multiple of the "
                         "uniform share (e.g. 1.25; None = never)")
    ap.add_argument("--attn-impl", choices=("decode_kernel", "xla"),
                    default="decode_kernel",
                    help="decode attention: ragged Pallas kernel (streams "
                         "ceil(len/bc) KV blocks per slot) or dense SDPA")
    ap.add_argument("--kv-layout", choices=("dense", "paged"),
                    default="dense",
                    help="KV memory: dense [B, max_context] rows or "
                         "block-granular pages with shared-prefix reuse")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many shared system-prompt tokens "
                         "to every request (shows the paged prefix cache)")
    ap.add_argument("--interleave", choices=("streams", "off"),
                    default="streams",
                    help="DEP executor emission: 'streams' interleaves "
                         "the r1 micro-batch streams in scheduled start "
                         "order; 'off' runs them back-to-back "
                         "(bit-identical outputs, different overlap)")
    ap.add_argument("--trace-out", default=None, metavar="OUT.json",
                    help="record engine spans (phases, request "
                         "lifecycles) and write a Chrome-trace/Perfetto "
                         "JSON file")
    ap.add_argument("--metrics-out", default=None, metavar="OUT.jsonl",
                    help="append metrics-registry snapshots to this "
                         "JSONL file")
    ap.add_argument("--metrics-interval", type=float, default=None,
                    metavar="SECONDS",
                    help="with --metrics-out: snapshot every N seconds "
                         "while serving (default: one final snapshot)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch)
    store = ProfileStore(args.profile_store)
    policy = None
    if cfg.is_moe:
        # a TPU plans from its kind's registry profile (an unknown kind
        # raises); on the CPU the paper's A6000 fit stands in, as a
        # planning tool only
        dev = jax.devices()[0]
        hw = profile_for_device(dev) if dev.platform == "tpu" \
            else PAPER_A6000
        planner = FinDEPPlanner(cfg, DepClusterConfig(8, 3, 5), hw,
                                PlannerConfig(mem_cap_samples=8))
        policy = make_policy(args.policy, planner, static_seq_len=256)
    # the engine owns the measured-cost-model flow: calibrate= measures +
    # persists, profile= loads a stored/registry fit (no re-measurement)
    eng = ServingEngine(cfg, num_slots=args.slots, max_context=256,
                        plan_policy=policy, admission=args.admission,
                        token_budget=args.token_budget,
                        calibrate=args.calibrate, profile=args.profile,
                        profile_store=store,
                        drift_threshold=args.drift_threshold,
                        attn_impl=args.attn_impl,
                        kv_layout=args.kv_layout,
                        replicate_hot_k=args.replicate_hot_k,
                        rebalance_threshold=args.rebalance_threshold,
                        tracer=bool(args.trace_out),
                        interleave=args.interleave,
                        dtype=jnp.float32)
    if eng.calibration is not None:
        res = eng.calibration
        r2s = {k: round(v, 4) for k, v in res.fit_r2.items()}
        print(f"calibrated {res.profile.name!r} in {res.wall_s:.1f}s "
              f"(R^2 {r2s}"
              + (", comm=proxy" if res.comm_is_proxy else "")
              + f") -> {store.root}")
    elif args.profile:
        print(f"planning from profile {args.profile!r} "
              f"(store {store.root} or registry) — no re-measurement")

    rng = np.random.RandomState(0)
    system = list(rng.randint(0, cfg.vocab_size, size=args.shared_prefix))
    reqs = []
    for i in range(args.requests):
        prompt = system + list(rng.randint(0, cfg.vocab_size,
                                           size=rng.randint(4, 48)))
        reqs.append(Request(prompt=prompt, max_new_tokens=args.max_new,
                            temperature=0.0 if i % 2 == 0 else 0.8))
        eng.submit(reqs[-1])

    t0 = time.perf_counter()
    if args.metrics_out and args.metrics_interval:
        # periodic snapshots while serving (one JSONL line each)
        start = len(eng.finished)
        last_snap = t0
        while True:
            progressed = eng.step()
            now = time.perf_counter()
            if now - last_snap >= args.metrics_interval:
                eng.metrics.export_jsonl(args.metrics_out)
                last_snap = now
            if not progressed and not eng.waiting:
                break
        finished = eng.finished[start:]
    else:
        finished = eng.run()
    dt = time.perf_counter() - t0

    done = sum(len(r.output) for r in reqs)
    ttfts = [r.ttft for r in reqs if r.ttft is not None]
    print(f"\nserved {len(finished)}/{args.requests} requests / "
          f"{done} tokens in {dt:.1f}s -> {done/dt:.1f} tokens/s decode")
    print(f"TTFT: mean {np.mean(ttfts)*1e3:.0f} ms, "
          f"p90 {np.percentile(ttfts, 90)*1e3:.0f} ms")
    if eng.metrics is not None:
        # the registry's histograms over every finished request
        def _pcts(name):
            h = eng.metrics.histogram(name)
            return h.p50, h.p99, h.count
        t50, t99, tn = _pcts("repro_engine_ttft_seconds")
        p50, p99, pn = _pcts("repro_engine_tpot_seconds")
        if tn:
            print(f"TTFT p50 {t50*1e3:.0f} ms, p99 {t99*1e3:.0f} ms "
                  f"(n={tn}, log-bucket estimate)")
        if pn:
            print(f"TPOT p50 {p50*1e3:.0f} ms, p99 {p99*1e3:.0f} ms "
                  f"(n={pn})")
    print(f"first outputs: {[r.output[:6] for r in reqs[:3]]}")

    if eng.plan_cache is not None:
        s = eng.plan_cache.stats
        print(f"\npolicy={args.policy} admission={args.admission}: "
              f"{len(eng.plan_cache)} shapes resolved, "
              f"{s.hits} cache hits ({s.hit_rate:.0%}), "
              f"{s.solve_time_total*1e3:.1f} ms total solve time")
        entries = eng.resolved_plans().items()
        prefills = sorted(k for k, _ in entries if k[0] == "prefill")
        decodes = sorted(k for k, _ in entries if k[0] == "decode")
        plans = dict(entries)
        for key in prefills:
            phase, bucket, batch = key[:3]
            skew = f" skew={key[3]!r}" if len(key) > 3 else ""
            p = plans[key]
            print(f"  {phase:>7} bucket={bucket:<5} batch={batch}: "
                  f"m_a={p.m_a} r1={p.r1} r2={p.r2} {p.order}{skew}")
        for key in decodes:
            phase, occ = key[:2]
            skew = f" skew={key[2]!r}" if len(key) > 2 else ""
            p = plans[key]
            print(f"  {phase:>7} {occ!r}: "
                  f"m_a={p.m_a} r1={p.r1} r2={p.r2} {p.order}{skew}")

    load = eng.expert_load()
    if load is not None:
        pl = eng.placement
        print(f"\nexpert load: imbalance {load['imbalance']:.2f}x uniform "
              f"(worst rank {load['rank_imbalance']:.2f}x), "
              f"{eng.stats.dropped_tokens} assignments dropped, "
              f"placement epoch {int(load['epoch'])}"
              + (f" (hot experts {pl.replicated})"
                 if pl is not None and pl.replicated else ""))

    paging = eng.paging_stats()
    if paging is not None:
        print(f"\npaged KV (block={paging['block_size']}): "
              f"{paging['blocks_used']}/{paging['blocks_usable']} pages "
              f"({paging['utilization']:.0%} pinned), prefix hit-rate "
              f"{paging['prefix_hit_rate']:.0%} "
              f"({paging['prefix_hit_tokens']} tokens), "
              f"{paging['preemptions']} preemptions")

    if eng.telemetry is not None and eng.telemetry.phases:
        print("\ntelemetry (predicted vs measured):")
        for phase, st in sorted(eng.telemetry.summary().items()):
            res = st["residual"]
            print(f"  {phase:>7}: n={st['count']:<4} "
                  f"measured={st['measured_s']:.3f}s "
                  f"predicted={st['predicted_s']:.3f}s "
                  + (f"residual={res:+.1%}" if res is not None else
                     "residual=n/a"))
    if eng.drift is not None:
        eng.drift.refresher.drain()
        ds, cs = eng.drift.stats, eng.plan_cache.stats
        print(f"drift: {ds.drift_events} events over {ds.observations} "
              f"observations -> {cs.refreshes} background re-solves "
              f"(threshold {args.drift_threshold:+.0%})")
        eng.close()

    if args.trace_out and eng.tracer is not None:
        from repro.obs import export_chrome_trace, validate_chrome_trace
        obj = export_chrome_trace(args.trace_out, tracer=eng.tracer,
                                  meta={"arch": args.arch,
                                        "policy": args.policy})
        stats = validate_chrome_trace(obj)
        print(f"\nwrote trace {args.trace_out}: {stats['complete']} spans "
              f"on {stats['tracks']} tracks (open in ui.perfetto.dev)")
    if args.metrics_out and eng.metrics is not None:
        eng.metrics.export_jsonl(args.metrics_out)
        print(f"wrote metrics snapshot -> {args.metrics_out}")


if __name__ == "__main__":
    main()
