"""One run of one benchmark cell, in one process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

1. Finds the cell in ``BENCHMARK.json``, its configuration file and its
   traffic mix; refuses a machine whose JAX finds no TPU, or fewer chips
   than the cell asks for.
2. Makes the weights on the chip from ``--seed`` and builds the system
   under test (``bench/systems/<family>.py``).
3. Warms up every shape the cell's traffic can use, then resets the
   system's statistics. All of that, from process start, is ``setup_s``.
4. Drives the window: an open loop submits each request at its due time;
   between submissions the harness steps the system and stamps each new
   output token when the step returns. With ``--trace 1``
   the last few seconds of the window are traced by the profiler.
5. Reads the peak device memory, frees the system's state, and decides
   ``correct`` (``correctness.py``).
6. Prints the numbers compared, each with its limit, as the last lines of
   standard error, and one JSON object as the last line of standard
   output: end-to-end metrics with ``--trace 0``, per-layer metrics with
   ``--trace 1``. Every metric but ``setup_s`` has a reader of its own,
   ``bench/metrics/<name>.py``, found by its name in ``BENCHMARK.json``.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from collections import deque
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Callable, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: a request due in the window that has no first token when the window
#: closes is still served, for at most this long, so that its latency is
#: its own and not a cut
DRAIN_S = 60.0


class Refused(RuntimeError):
    """The run cannot be made here (no chip, wrong cell): no result."""


def load_module(path: str, name: str):
    """The module at ``path`` under ``name``, loaded once a process."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# what one run records
# ---------------------------------------------------------------------------

@dataclass
class Served:
    """One request as the harness saw it."""

    spec: object                        # traffic RequestSpec
    req: object                         # the system's request: output,
    #                                     admit_t, finish_t, error
    due: float                          # host clock
    submit_t: float
    token_t: List[float] = field(default_factory=list)

    @property
    def prompt_len(self) -> int:
        return len(self.spec.prompt)


@dataclass
class StepRecord:
    index: int
    start: float
    end: float
    keys: List[int]                     # context length of each decoded row


@dataclass
class Run:
    """Everything a metric reader may read."""

    cfg: dict
    model: object                        # bench/models/<family>.py
    peaks: dict
    chips: int
    seconds: float
    t0: float                            # window start, host clock
    t1: float                            # window end
    served: List[Served]
    steps: List[StepRecord]
    decode_s: float                      # the system's own timings of the
    decode_n: int                        # window, taken when it closed
    prefill_s: float
    trace: Optional[object] = None       # trace_reduce.Reduction
    traced_steps: List[StepRecord] = field(default_factory=list)

    def in_window(self, t: float) -> bool:
        return self.t0 <= t < self.t1

    def due_in_window(self) -> List[Served]:
        return [s for s in self.served if self.in_window(s.due)]


def percentile(values, q: float) -> Optional[float]:
    """Nearest-rank percentile (``q`` in 0..100); None when empty."""
    v = sorted(values)
    if not v:
        return None
    return float(v[max(int(math.ceil(q / 100.0 * len(v))) - 1, 0)])


# ---------------------------------------------------------------------------
# compiles
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts programs lowered (traced to MLIR), which every compile and
    every persistent-cache hit begins with."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _installed: List["CompileCounter"] = []

    def __init__(self):
        self.count = 0
        if not CompileCounter._installed:
            import jax
            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._listen)
        CompileCounter._installed[:] = [self]

    @staticmethod
    def _listen(event, duration, **kw):
        if event == CompileCounter.EVENT:
            for c in CompileCounter._installed:
                c.count += 1


def enable_compile_cache() -> None:
    """JAX's persistent cache at the checkout's fixed ``.jax_cache``, for
    every program however quick to compile (the eager prefill compiles
    one small program per operation and shape)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------------------
# driving the system
# ---------------------------------------------------------------------------

class Client:
    """The load side: submits requests to the system under test, steps it,
    and stamps each output token when the step that made it returns."""

    def __init__(self, system, clock=time.perf_counter):
        self.system = system
        self.clock = clock
        self.active: List[Served] = []
        self.served: List[Served] = []
        self.steps: List[StepRecord] = []
        self.annotate = False

    def submit(self, spec, due: float) -> Served:
        t = self.clock()
        s = Served(spec=spec, req=self.system.submit(spec, due), due=due,
                   submit_t=t)
        self.active.append(s)
        self.served.append(s)
        return s

    def busy(self) -> bool:
        return self.system.busy()

    def step(self) -> None:
        start = self.clock()
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation("bench.step"):
                self.system.step()
        else:
            self.system.step()
        end = self.clock()
        index = len(self.steps)
        keys = []
        still = []
        for s in self.active:
            n = len(s.req.output)
            while len(s.token_t) < n:
                keys.append(s.prompt_len + len(s.token_t))
                s.token_t.append(end)
            if s.req.finish_t is None:
                still.append(s)
        self.active = still
        self.steps.append(StepRecord(index, start, end, keys))

    def finished(self) -> List[Served]:
        return [s for s in self.served if s.req.finish_t is not None]


def warm_up(client: Client, combos, mix: dict, vocab: int,
            log: Callable, lowered: Callable[[], int] = lambda: 0) -> int:
    """Run every prefill group shape the mix can produce (``combos``:
    bucket, rows, prompt tokens), and every decode program (greedy only,
    or with top-k sampling) it uses, through the system itself; each
    warm-up request asks for one token."""
    sampled = float(mix.get("greedy_share", 1.0)) < 1.0
    rng = np.random.default_rng(0)
    t = time.perf_counter()
    for i, (bucket, rows, cost) in enumerate(combos):
        specs = []
        for j in range(rows):
            topk = sampled and (i + j) % 2 == 1
            specs.append(SimpleNamespace(
                prompt=rng.integers(0, vocab, cost, dtype=np.int32),
                max_new_tokens=1,
                temperature=float(mix["sampled"]["temperature"])
                if topk else 0.0,
                top_k=int(mix["sampled"]["top_k"]) if topk else 0))
        t1, n1 = time.perf_counter(), lowered()
        for s in specs:
            client.submit(s, time.perf_counter())
        while client.busy():
            client.step()
        log(f"warm-up {i + 1}/{len(combos)}: bucket {bucket} x {rows} "
            f"rows, {lowered() - n1} programs, "
            f"{time.perf_counter() - t1:.1f} s")
    log(f"warm-up: {len(combos)} prefill group shapes "
        f"{[(b, n) for b, n, _ in combos]} in "
        f"{time.perf_counter() - t:.1f} s")
    client.served.clear()
    client.steps.clear()
    return len(combos)


class Tracer:
    """The profiler over the last few seconds of the window: ``poll``
    starts it, ``close`` ends the traced window when the window closes,
    and ``stop`` stops the profiler after the drain, because stopping it
    holds the host for seconds (9 s on a v5e host), which no measured
    request may wait through."""

    def __init__(self, client: Client, start: float):
        self.client = client
        self.start_at = start
        self.dir = None
        self.state = "before"
        self.first_step = self.last_step = 0
        self._mark = None

    def poll(self, now: float) -> None:
        import jax
        if self.state == "before" and now >= self.start_at:
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            # host marks and device operations; no Python call tracing,
            # which would slow the very host loop the trace attributes
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._mark = jax.profiler.TraceAnnotation("bench.traced")
            self._mark.__enter__()
            self.client.annotate = True
            self.first_step = len(self.client.steps)
            self.state = "on"

    def close(self) -> None:
        if self.state != "on":
            return
        self.last_step = len(self.client.steps)
        self.client.annotate = False
        self._mark.__exit__(None, None, None)
        self.state = "closed"

    def stop(self) -> None:
        import jax
        self.close()
        if self.state == "closed":
            jax.profiler.stop_trace()
            self.state = "done"


def drive_open(client: Client, specs, seconds: float, tracer=None):
    """Submit each request at its due time; step while there is work.
    Returns (t0, t1) of the window."""
    clock = client.clock
    pending = deque(specs)
    t0 = clock()
    t1 = t0 + seconds
    while True:
        now = clock()
        if now >= t1:
            break
        if tracer is not None:
            tracer.poll(now)
        while pending and t0 + pending[0].due_s <= now:
            spec = pending.popleft()
            client.submit(spec, t0 + spec.due_s)
        if client.busy():
            client.step()
        else:
            nxt = t0 + pending[0].due_s if pending else t1
            time.sleep(max(min(nxt, t1) - clock(), 0.0))
    return t0, t1


def drain(client: Client, t0: float, t1: float) -> None:
    """Serve on, with no new request, until every request due in the
    window has its first token (at most ``DRAIN_S``)."""
    limit = client.clock() + DRAIN_S
    while client.clock() < limit and client.busy() and any(
            t0 <= s.due < t1 and not s.token_t and s.req.error is None
            for s in client.served):
        client.step()


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def find_cell(spec: dict, workload: str):
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json "
                      f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    return cell, configs[cell["config"]]


def metrics_for(spec: dict, workload: str, trace: bool) -> List[dict]:
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def device_json(devices, peak: int, trace=None) -> dict:
    d = {"platform": devices[0].platform, "kind": devices[0].device_kind,
         "count": len(devices), "memory_peak_bytes": int(peak)}
    if trace is not None:
        d["busy_s"] = trace.busy_s
        d["window_s"] = trace.window_s
    return d


class Session:
    """A cell's system under test on this process's chips: its files, the
    weights of one seed, and the system warmed for the cell's traffic.
    ``run_cell`` makes one window with it; the chip tools ``sweep.py`` and
    ``control.py`` make several. The keywords are for tests: a CPU run
    (``require_tpu=False`` with its own ``peaks``), no persistent cache,
    traffic mixes from ``mix_dir``."""

    def __init__(self, spec: dict, workload: str, *,
                 require_tpu: bool = True, peaks: Optional[dict] = None,
                 cache: bool = True, mix_dir: Optional[str] = None,
                 log: Callable = print):
        import jax
        self.spec = spec
        self.log = log
        self.cell, conf = find_cell(spec, workload)
        devices = jax.devices()
        if require_tpu and devices[0].platform != "tpu":
            raise Refused(f"JAX finds no TPU (platform "
                          f"{devices[0].platform!r})")
        self.chips = int(self.cell["chips"])
        if len(devices) < self.chips:
            raise Refused(f"the cell asks for {self.chips} chips; JAX "
                          f"finds {len(devices)}")
        self.devices = devices[:self.chips]
        kind = self.devices[0].device_kind
        if peaks is None:
            table = load_json(os.path.join(BENCH, "peaks.json"))
            if kind not in table:
                raise Refused(f"no peaks for device kind {kind!r} in "
                              "bench/peaks.json")
            peaks = table[kind]
        self.peaks = peaks
        if cache:
            enable_compile_cache()
        self.counter = CompileCounter()
        log(f"devices: {len(self.devices)} x {kind} "
            f"({self.devices[0].platform}); cell {self.cell['name']} on "
            f"{self.chips} chip(s)")
        self.cfg = load_json(os.path.join(ROOT, conf["file"]))
        self.mix = load_json(os.path.join(
            mix_dir or os.path.join(BENCH, "traffic"),
            self.cell["traffic"] + ".json"))
        family = self.cfg["family"]
        self.model = load_module(os.path.join(BENCH, "models",
                                              family + ".py"),
                                 "bench_model_" + family)
        self.system = load_module(os.path.join(BENCH, "systems",
                                               family + ".py"),
                                  "bench_system_" + family)
        self.gen = load_module(os.path.join(BENCH, "traffic",
                                            "generator.py"),
                               "bench_traffic")
        self.corr = load_module(os.path.join(BENCH, "correctness.py"),
                                "bench_correctness")
        self.dtype = self.model.DTYPES[self.cfg["serving"]["dtype"]]
        self.vocab = int(self.cfg["vocab_size"])
        self.params = self.sut = self.client = None

    def build(self, seed: int) -> None:
        """Weights from ``seed`` and a warmed system over them."""
        import jax
        import jax.numpy as jnp
        self.free()
        self.params = None
        t = time.perf_counter()
        self.params = self.model.make_params(self.cfg, seed, self.dtype)
        jax.block_until_ready(self.params)
        nbytes = self.model.weight_bytes(self.cfg,
                                         jnp.dtype(self.dtype).itemsize)
        self.log(f"weights: {nbytes} bytes made from the seed in "
                 f"{time.perf_counter() - t:.1f} s")
        self.sut = self.system.System(self.cfg, self.params,
                                      dtype=self.dtype, seed=seed)
        self.client = Client(self.sut)
        combos = self.system.prefill_groups(
            self.cfg, int(self.mix["prompt"]["min"]),
            int(self.mix["prompt"]["max"]))
        warm_up(self.client, combos, self.mix, self.vocab, self.log,
                lambda: self.counter.count)

    def free(self) -> None:
        """Drop the system under test and its state; the weights stay."""
        if self.sut is not None:
            self.sut.free()
        self.sut = self.client = None

    def requests(self, seed: int, seconds: float,
                 rate: Optional[float] = None):
        return self.gen.generate(self.mix, seed, seconds, self.vocab, rate)

    def window(self, specs, seconds: float, tracer=None):
        """Serve ``specs`` for ``seconds`` from now, then each request due
        in it to its first token. Returns (t0, t1) and the system's own
        timings of the window, taken when it closed."""
        t0, t1 = drive_open(self.client, specs, seconds, tracer)
        if tracer is not None:
            tracer.close()
        timings = self.sut.timings()
        drain(self.client, t0, t1)
        if tracer is not None:
            tracer.stop()
        return t0, t1, timings

    def greedy_finished(self):
        return [s.req for s in self.client.finished()
                if s.spec.temperature == 0.0 and s.req.output
                and s.req.error is None]

    def sample(self, finished, seed: int):
        return self.corr.sample_requests(
            finished, seed, int(self.cfg["compare"]["min_tokens"]))

    def memory_peak(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)


def run_cell(args, *, spec: dict, t_start: float, log: Callable = print,
             **session_kw) -> dict:
    """One run; returns the result object (the contract's last line)."""
    ses = Session(spec, args.workload, log=log, **session_kw)
    ses.build(args.seed)
    client = ses.client
    specs = ses.requests(args.seed, args.seconds)
    if args.trace:
        ses.sut.record_phases()
    ses.sut.reset_stats()
    lowered0 = ses.counter.count
    setup_s = time.perf_counter() - t_start
    log(f"setup: {setup_s:.3f} s from process start; "
        f"{lowered0} programs lowered in set-up")

    tracer = None
    if args.trace:
        tracer = Tracer(client, time.perf_counter() + args.seconds
                        - min(5.0, 0.3 * args.seconds))
    t0, t1, timings = ses.window(specs, args.seconds, tracer)
    lowered_window = ses.counter.count - lowered0
    phases = ses.sut.phases()
    log(f"window: {t1 - t0:.3f} s, {len(client.steps)} steps with "
        f"the drain, {lowered_window} programs lowered in the window")
    late = [s.submit_t - s.due for s in client.served if t0 <= s.due < t1]
    if late:
        log(f"generator lateness: p50 {percentile(late, 50) * 1e3:.3f} ms,"
            f" p90 {percentile(late, 90) * 1e3:.3f} ms, max "
            f"{max(late) * 1e3:.3f} ms over {len(late)} requests")
    peak = ses.memory_peak()
    log(f"memory: peak {peak} bytes in use on the fullest chip")
    run = Run(cfg=ses.cfg, model=ses.model,
              peaks=ses.peaks, chips=ses.chips, seconds=args.seconds,
              t0=t0, t1=t1, served=client.served, steps=client.steps,
              **timings)

    if tracer is not None and tracer.dir is not None:
        tr = load_module(os.path.join(BENCH, "trace_reduce.py"),
                         "bench_trace_reduce")
        run.traced_steps = client.steps[tracer.first_step:tracer.last_step]
        t = time.perf_counter()
        try:
            path = tr.find_xplane(tracer.dir)
            run.trace = tr.reduce_file(
                path, phases=phases,
                step_host_starts=[s.start for s in run.traced_steps])
            if getattr(args, "keep_trace", None):
                keep_excerpt(tr, path, args.keep_trace, run.traced_steps,
                             phases)
        except (ValueError, FileNotFoundError) as e:
            log(f"trace: nothing to reduce ({e})")
        finally:
            shutil.rmtree(tracer.dir, ignore_errors=True)
        if run.trace is not None:
            log(f"trace: {len(run.traced_steps)} steps over "
                f"{run.trace.window_s:.3f} s, busy {run.trace.busy_s:.4f} s"
                f", kernels {run.trace.kernel_count} {run.trace.kernel_s}; "
                f"reduced in {time.perf_counter() - t:.1f} s")
            log(f"idle by host phase (s): {run.trace.idle_by_phase}")

    # correctness, with the system's state freed
    attempted, failed = _counts(run)
    finished = ses.greedy_finished()
    ses.free()
    del client
    t = time.perf_counter()
    sample = ses.sample(finished, args.seed)
    gaps = ses.corr.served_gaps(ses.model, ses.params, ses.cfg, sample)
    values = ses.corr.numbers(gaps)
    ok, report = ses.corr.judge(values, ses.cfg["compare"]["limits"])
    log(f"compared {len(gaps)} served tokens of {len(sample)} greedy "
        f"requests (of {len(finished)} finished) with the float32 "
        f"reference in {time.perf_counter() - t:.1f} s: {values}")

    metrics = {}
    for m in metrics_for(spec, ses.cell["name"], bool(args.trace)):
        if m["name"] == "setup_s":
            value = setup_s
        else:
            reader = load_module(os.path.join(BENCH, "metrics",
                                              m["name"] + ".py"),
                                 "bench_metric_" + m["name"])
            value = reader.read(run)
        if value is None:
            continue
        if not math.isfinite(value):
            ok = False
            log(f"{m['name']} is not finite ({value})")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": bool(ok), "attempted": attempted, "failed": failed,
              "metrics": metrics,
              "device": device_json(ses.devices, peak, run.trace)}
    if run.trace is not None:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in run.trace.top_ops],
            "idle_gaps": [[n, s] for n, s in run.trace.idle_gaps]}
    result["compared"] = report
    return result


def keep_excerpt(tr, path: str, out: str, steps, phases, first: int = 3,
                 n: int = 3) -> None:
    """Write ``n`` traced steps from the ``first``, with the host-clock
    phase spans and step starts that belong to them, as a small recorded
    trace (``out``.pbtxt and ``out``.json) for a test of the reduction."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    _, marks = tr.host_marks(planes)
    sel = marks[first:first + n]
    host = steps[first:first + n]
    lo, hi = sel[0][0], sel[-1][1]
    with open(out + ".pbtxt", "w") as f:
        f.write(tr.excerpt(planes, lo, hi))
    a, b = host[0].start, host[-1].end
    with open(out + ".json", "w") as f:
        json.dump({"step_host_starts": [s.start - a for s in host],
                   "phases": [(nm, x - a, y - a) for nm, x, y in phases
                              if x < b and y > a]}, f)


def _counts(run: Run):
    """Requests the window was meant to serve (those due in it), and those
    that failed: refused by the system, or never given a first token."""
    due = run.due_in_window()
    failed = sum(1 for s in due
                 if s.req.error is not None or not s.token_t)
    return len(due), failed


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="PATH",
                    help="with --trace 1, also write a few traced steps "
                    "to PATH.pbtxt and PATH.json (a recorded trace for "
                    "the reduction's test)")
    return ap.parse_args(argv)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    try:
        result = run_cell(args, spec=spec, t_start=t_start,
                          log=lambda m: print(m, flush=True))
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    except ImportError as e:
        print(f"bench: the system under test is not in this checkout "
              f"({e})", file=sys.stderr)
        return 2
    for name, r in result["compared"].items():
        print(f"compared {name}: {r['value']} (limit {r['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
