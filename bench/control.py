#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` for a dozen seeds or more: the
program's, and the precision control's on the same prompts and tokens.

    python3 bench/control.py --workload qwen2moe.chat --first-seed 100 \\
        --seeds 12 --seconds 30

One process for each seed, one after another, as each run is a process
of its own (the parent touches no JAX, so each child has the chip):
weights made from the seed, a warmed system, a short window at the
cell's own load, and, once it has closed and the system's state is
freed, the sample of finished greedy requests that a run compares. For
that sample it prints the program's numbers (served tokens against the
float32 reference) and the control's (the
reference with its weights rounded to float8 e4m3, the precision below
the served bfloat16, put in the program's place). The lower reading of a
limit is the largest the program gives over the seeds, the upper one the
smallest the control gives. The benchmark's own runs do not run this.
"""
import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

CONTROL_DTYPE = "float8_e4m3fn"


def readings(ses, seed: int, seconds: float) -> dict:
    ses.build(seed)
    ses.window(ses.requests(seed, seconds), seconds)
    finished = ses.greedy_finished()
    ses.free()
    sample = ses.sample(finished, seed)
    t = time.perf_counter()
    prog = ses.corr.served_gaps(ses.model, ses.params, ses.cfg, sample)
    t_ref = time.perf_counter() - t
    ctrl = ses.corr.control_gaps(ses.model, ses.params, ses.cfg, sample,
                                 ses.model.DTYPES[CONTROL_DTYPE])
    return {"seed": seed, "requests": len(sample), "tokens": len(prog),
            "program": ses.corr.numbers(prog),
            "control": ses.corr.numbers(ctrl),
            "program_exact_share": float((prog == 0).mean()),
            "control_exact_share": float((ctrl == 0).mean()),
            "reference_s": t_ref}


def one_seed(args) -> dict:
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    ses = harness.Session(spec, args.workload,
                          log=lambda m: print(m, flush=True))
    row = readings(ses, args.first_seed, args.seconds)
    row["limits"] = ses.cfg["compare"]["limits"]
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--one", action="store_true",
                    help="read --first-seed alone, in this process")
    args = ap.parse_args(argv)
    if args.one:
        print(json.dumps(one_seed(args)), flush=True)
        return 0
    rows = []
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--one",
             "--workload", args.workload, "--first-seed", str(seed),
             "--seconds", str(args.seconds)],
            stdout=subprocess.PIPE, text=True)
        last = (p.stdout.strip().splitlines() or [""])[-1]
        print(p.stdout, end="", flush=True)
        if p.returncode != 0 or not last.startswith("{"):
            print(json.dumps({"seed": seed, "failed": p.returncode}),
                  flush=True)
            continue
        rows.append(json.loads(last))
    if not rows:
        return 1
    for name in rows[0]["program"]:
        print(json.dumps({
            "number": name, "seeds": len(rows),
            "program_max": max(r["program"][name] for r in rows),
            "control_min": min(r["control"][name] for r in rows),
            "limit": rows[0]["limits"].get(name)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
