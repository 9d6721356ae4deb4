#!/usr/bin/env python3
"""Find an open-loop cell's knee on the chip: the highest arrival rate the
system sustains without a growing backlog.

    python3 bench/sweep.py --workload qwen2moe.chat --seed 5 --seconds 30 \\
        --rates 1.5,2,2.5,3,3.5

One process builds the cell once (weights, engine, warm-up) and serves one
window per rate, the same mix at that rate; between windows it serves
every request to the end. For each rate it prints one JSON line: requests
due, their TTFT's median and 90th percentile (each request served to its
first token), the same over the window's first and second halves, the
requests due in the window but not admitted when it closed, and the
decode steps' mean. A backlog that grows shows as a second half slower
than the first and requests left waiting. The benchmark's own runs do not
run this; its result is the mix file's ``rate_per_s``, set at about four
fifths of the knee.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def one_rate(ses, rate: float, seconds: float, seed: int) -> dict:
    specs = ses.requests(seed, seconds, rate=rate)
    ses.sut.reset_stats()
    ses.client.served.clear()
    ses.client.steps.clear()
    t0, t1, timings = ses.window(specs, seconds)
    due = [s for s in ses.client.served if t0 <= s.due < t1]
    mid = t0 + seconds / 2

    def ttft(group, q):
        return harness.percentile(
            [(s.token_t[0] - s.due) * 1e3 for s in group if s.token_t], q)
    out = {"rate_per_s": rate, "due": len(due),
           "ttft_p50_ms": ttft(due, 50), "ttft_p90_ms": ttft(due, 90),
           "ttft_p90_first_half_ms": ttft([s for s in due if s.due < mid],
                                          90),
           "ttft_p90_second_half_ms": ttft([s for s in due if s.due >= mid],
                                           90),
           "unadmitted_at_close": sum(
               1 for s in due if s.req.admit_t is None
               or s.req.admit_t >= t1),
           "decode_step_ms": 1e3 * timings["decode_s"]
           / max(timings["decode_n"], 1)}
    while ses.client.busy():                 # serve everything out
        ses.client.step()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    ses = harness.Session(spec, args.workload,
                          log=lambda m: print(m, flush=True))
    ses.build(args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        t = time.perf_counter()
        res = one_rate(ses, rate, args.seconds, args.seed)
        res["wall_s"] = time.perf_counter() - t
        print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
