"""Live CPU serving throughput: the end-to-end engine on a reduced MoE
model (real execution, not simulation) with per-shape online scheduling
through the pluggable policy layer (--policy) and pluggable request
admission (--admission fcfs|spf|token_budget, --token-budget N). Decode
plans are resolved per KV-ledger occupancy summary, so a churn workload
(mixed prompt/output lengths) exercises >= 2 distinct decode solves."""
from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row
from repro.configs import get_smoke_config
from repro.configs.base import DepClusterConfig
from repro.core import FinDEPPlanner, PAPER_A6000
from repro.core.planner import PlannerConfig
from repro.runtime import ADMISSIONS, Request, ServingEngine
from repro.sched import POLICIES, make_policy

MAX_CONTEXT = 128


def run(policy: str = "findep", admission: str = "fcfs",
        token_budget=None):
    rows = []
    info = {}
    for arch in ("qwen2-moe-a2.7b", "qwen2-1.5b"):
        cfg = get_smoke_config(arch)
        pol = None
        if cfg.is_moe:
            planner = FinDEPPlanner(cfg, DepClusterConfig(8, 3, 5),
                                    PAPER_A6000,
                                    PlannerConfig(mem_cap_samples=8))
            pol = make_policy(policy, planner, static_seq_len=MAX_CONTEXT)
        eng = ServingEngine(cfg, num_slots=4, max_context=MAX_CONTEXT,
                            plan_policy=pol, admission=admission,
                            token_budget=token_budget, dtype=jnp.float32)
        # warmup compiles prefill/decode; reset so idle/compile time is
        # not billed to throughput (reset_stats also clears the StepTimer
        # EWMAs the old stats.reset() left carrying warmup samples)
        eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
        eng.run()
        eng.reset_stats()
        rng = np.random.RandomState(0)
        # churn: mixed prompt lengths (buckets 64 and 128) and staggered
        # finishes, so the decode composition actually varies
        reqs = [Request(prompt=list(rng.randint(0, cfg.vocab_size,
                                                size=rng.randint(4, 110))),
                        max_new_tokens=int(rng.randint(8, 24)))
                for _ in range(8)]
        for r in reqs:
            eng.submit(r)
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        tok = eng.stats.decode_tokens
        sched = ""
        if eng.plan_cache is not None:
            s = eng.plan_cache.stats
            decode_keys = [k for k in eng.resolved_plans()
                           if k[0] == "decode"]
            info[f"{arch}.decode_resolutions"] = len(decode_keys)
            sched = (f";policy={policy};admission={admission};"
                     f"plans={len(eng.plan_cache)};"
                     f"decode_resolutions={len(decode_keys)};"
                     f"hit_rate={s.hit_rate:.2f};"
                     f"solve_ms={s.solve_time_total*1e3:.1f}")
        rows.append(csv_row(
            f"serving_engine.{arch}", dt / max(tok, 1) * 1e6,
            f"decode_tokens={tok};tokens_per_s={tok/dt:.1f};"
            f"ttft_ms={np.mean([r.ttft for r in reqs])*1e3:.1f}" + sched))
    return rows, info


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", choices=POLICIES, default="findep")
    ap.add_argument("--admission", choices=ADMISSIONS, default="fcfs")
    ap.add_argument("--token-budget", type=int, default=None)
    args = ap.parse_args()
    rows, info = run(policy=args.policy, admission=args.admission,
                     token_budget=args.token_budget)
    for r in rows:
        print(r)
    if info:
        print(f"# {info}")
