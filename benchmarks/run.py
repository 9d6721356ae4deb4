"""Benchmark harness: one module per paper table (+ solver latency,
perf-model fit, live engine, kernel block sweep).

Prints ``name,us_per_call,derived`` CSV rows and a summary of the paper
claims checked. Usage: PYTHONPATH=src python -m benchmarks.run [names...]
"""
from __future__ import annotations

import sys

MODULES = [
    "perf_model_fit",
    "table3_ma",
    "table4_r1",
    "table5_throughput",
    "table6_online",
    "table7_overlap",
    "plan_trace",
    "solver_latency",
    "policy_sweep",
    "regime_sweep",
    "serving_engine",
    "kernel_blocks",
    "decode_attention",
    "paged_kv",
    "expert_load",
    "obs_smoke",
    "analysis_gate",
]


def main() -> None:
    import importlib

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    names = sys.argv[1:] or MODULES
    print("name,us_per_call,derived")
    summary = {}
    for name in names:
        mod = importlib.import_module(f"benchmarks.{name}")
        rows, info = mod.run()
        for r in rows:
            print(r, flush=True)
        summary.update({f"{name}.{k}": v for k, v in info.items()})
    print("\n# claim summary")
    for k, v in sorted(summary.items()):
        print(f"# {k} = {v}")


if __name__ == '__main__':
    main()
