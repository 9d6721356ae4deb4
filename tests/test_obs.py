"""Observability layer (repro.obs): metrics registry, span tracing,
Chrome-trace export, overlap attribution, and the engine wiring."""
import json

import numpy as np
import pytest

from repro.core.taskgraph import (LoweringSpec, TaskCosts, lower,
                                  lower_exec, schedule)
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                       TraceRecorder, attribute_overlap, chrome_trace,
                       executed_exposed_comm, interval_subtract,
                       interval_total, interval_union, log_buckets,
                       parse_prometheus, use_tracer,
                       validate_chrome_trace)
from repro.obs.trace import Span, active_tracer


class _Plan:
    r1, r2, order, m_e = 2, 3, "ASAS", 4


def _costs():
    return TaskCosts(attn=2e-3, shared=1e-3, exp=3e-3, comm=2.5e-3)


# ---------------------------------------------------------------------------
# metrics: histogram buckets + quantiles
# ---------------------------------------------------------------------------

def test_log_buckets_boundaries():
    b = log_buckets(1e-5, 100.0, per_decade=3)
    assert b[0] == pytest.approx(1e-5)
    assert b[-1] == pytest.approx(100.0)
    # log-spaced: constant ratio of 10^(1/3) between boundaries
    ratios = [b[i + 1] / b[i] for i in range(len(b) - 1)]
    assert all(r == pytest.approx(10 ** (1 / 3), rel=1e-9)
               for r in ratios)
    # 7 decades at 3 per decade + the endpoint
    assert len(b) == 7 * 3 + 1


def test_histogram_bucket_edges():
    h = Histogram("h", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 1.0, 5.0, 10.0, 50.0, 1000.0):
        h.observe(v)
    # bisect_left: v <= boundary lands in that boundary's bucket
    assert h.bucket_counts == [2, 2, 1, 1]
    assert h.count == 6
    assert h.sum == pytest.approx(1066.5)


def test_histogram_quantiles_vs_numpy():
    rng = np.random.RandomState(7)
    vals = rng.lognormal(mean=-6.0, sigma=1.5, size=5000)
    h = Histogram("h")           # default log buckets, 3 per decade
    for v in vals:
        h.observe(v)
    ratio = 10 ** (1 / 3)        # one bucket width = max interp error
    for q in (0.5, 0.9, 0.99):
        est = h.quantile(q)
        exact = float(np.quantile(vals, q))
        assert exact / ratio <= est <= exact * ratio, \
            f"q={q}: est {est} vs exact {exact}"


def test_histogram_overflow_clamps():
    h = Histogram("h", buckets=(1.0, 2.0))
    for _ in range(10):
        h.observe(100.0)
    assert h.p50 == 2.0 and h.p99 == 2.0


# ---------------------------------------------------------------------------
# metrics: registry snapshot + reset + prometheus round-trip
# ---------------------------------------------------------------------------

def test_counter_gauge_snapshot_roundtrip():
    m = MetricsRegistry()
    c = m.counter("repro_test_events_total", "events")
    g = m.gauge("repro_test_queue_depth", "depth")
    c.inc(); c.inc(3)
    g.set(7.5)
    snap = m.snapshot()
    assert snap["repro_test_events_total"] == 4.0
    assert snap["repro_test_queue_depth"] == 7.5
    with pytest.raises(ValueError):
        c.inc(-1)
    # same (name, labels) returns the same object; mismatched type raises
    assert m.counter("repro_test_events_total") is c
    with pytest.raises(ValueError):
        m.gauge("repro_test_events_total")


def test_registry_source_and_reset_hook():
    m = MetricsRegistry()
    state = {"x": 2.0, "resets": 0}
    m.register_source("repro_src", lambda: {"x": state["x"]})
    m.register_reset(lambda: state.__setitem__("resets",
                                               state["resets"] + 1))
    c = m.counter("repro_test_total")
    c.inc(5)
    snap = m.snapshot()
    assert snap["repro_src_x"] == 2.0
    assert snap["repro_test_total"] == 5.0
    m.reset()
    assert state["resets"] == 1
    assert m.snapshot()["repro_test_total"] == 0.0


def test_prometheus_render_parse_roundtrip_with_escaping():
    m = MetricsRegistry()
    nasty = 'a"b\\c\nd'
    m.counter("repro_test_total", 'help with "quotes"',
              labels={"state": nasty}).inc(3)
    h = m.histogram("repro_test_seconds", buckets=(0.1, 1.0))
    h.observe(0.05); h.observe(0.5); h.observe(5.0)
    text = m.render_prometheus()
    samples = parse_prometheus(text)
    by_name = {}
    for name, labels, value in samples:
        by_name.setdefault(name, []).append((labels, value))
    assert by_name["repro_test_total"] == [({"state": nasty}, 3.0)]
    buckets = {lab["le"]: v
               for lab, v in by_name["repro_test_seconds_bucket"]}
    assert buckets["+Inf"] == 3.0         # cumulative
    assert buckets["0.1"] == 1.0 and buckets["1"] == 2.0
    assert by_name["repro_test_seconds_count"][0][1] == 3.0
    assert by_name["repro_test_seconds_sum"][0][1] == \
        pytest.approx(5.55)


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def test_span_recording_and_disabled_noop():
    t = [0.0]
    rec = TraceRecorder(clock=lambda: t[0])
    with rec.span("phase_a", track="engine", foo=1):
        t[0] = 1.5
    assert len(rec.spans) == 1
    s = rec.spans[0]
    assert (s.name, s.track, s.start, s.end) == ("phase_a", "engine",
                                                 0.0, 1.5)
    assert s.arg("foo") == 1
    off = TraceRecorder(enabled=False)
    with off.span("x"):
        pass
    off.instant("y")
    assert len(off) == 0


def test_active_tracer_scoping():
    assert active_tracer() is None
    rec = TraceRecorder()
    with use_tracer(rec):
        assert active_tracer() is rec
        with use_tracer(None):       # inner None shadows
            assert active_tracer() is None
        assert active_tracer() is rec
    assert active_tracer() is None
    off = TraceRecorder(enabled=False)
    with use_tracer(off):             # disabled recorder -> None
        assert active_tracer() is None


def test_request_lifecycle_spans():
    from repro.runtime.request import Request
    req = Request(prompt=[1, 2, 3], max_new_tokens=4)
    req.arrival_t, req.admit_t = 10.0, 11.0
    req.first_token_t, req.finish_t = 12.0, 14.0
    req.output = [5, 6, 7, 8]
    assert req.ttft == pytest.approx(2.0)
    assert req.tpot == pytest.approx(2.0 / 3)
    rec = TraceRecorder()
    rec.request_lifecycle(req)
    spans = {s.name: s for s in rec.by_cat("request")}
    assert spans["queued"].start == 10.0 and spans["queued"].end == 11.0
    assert spans["prefill"].end == 12.0
    assert spans["decode"].end == 14.0
    assert spans["decode"].arg("tokens") == 4


def test_dep_walk_emits_task_spans_under_tracer():
    from repro.core.dep import _walk_chunk_stream
    graph = lower_exec(3, "ASAS", 1)
    seen = []
    handlers = {k: seen.append
                for k in ("GATE", "A2E", "SHARED", "EXP", "E2A")}
    rec = TraceRecorder()
    with use_tracer(rec):
        _walk_chunk_stream(graph, handlers)
    emitted = rec.task_spans(emitted=True)
    assert len(emitted) == len(seen) == len(graph.exec_walk())
    assert [s.name for s in emitted] == [t.kind for t in seen]
    # without a tracer: same walk, zero spans
    seen2 = []
    _walk_chunk_stream(graph, {k: seen2.append for k in handlers})
    assert [t.kind for t in seen2] == [t.kind for t in seen]
    assert len(rec.task_spans(emitted=True)) == len(emitted)


# ---------------------------------------------------------------------------
# export + validation
# ---------------------------------------------------------------------------

def test_chrome_trace_export_and_validate(tmp_path):
    graph = lower(_Plan, LoweringSpec(T=2))
    res = schedule(graph, _costs())
    rec = TraceRecorder(clock=iter(np.arange(0, 100, 0.5)).__next__)
    with rec.span("step"):
        rec.instant("mark")
    obj = chrome_trace(tracer=rec, schedule=res)
    stats = validate_chrome_trace(obj)
    assert stats["complete"] == len(graph.tasks) + 1
    assert stats["tracks"] == 5          # 4 lanes + engine track
    # JSON string input works too
    validate_chrome_trace(json.dumps(obj))


def test_validate_rejects_partial_overlap_and_missing_keys():
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0, "dur": 10, "pid": 1, "tid": 0},
        {"name": "b", "ph": "X", "ts": 5, "dur": 10, "pid": 1, "tid": 0},
    ]}
    with pytest.raises(ValueError, match="partially overlaps"):
        validate_chrome_trace(bad)
    with pytest.raises(ValueError, match="missing key"):
        validate_chrome_trace({"traceEvents": [
            {"name": "a", "ph": "X", "ts": 0, "pid": 1, "tid": 0}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"noTraceEvents": []})
    # nested + disjoint are fine
    validate_chrome_trace({"traceEvents": [
        {"name": "o", "ph": "X", "ts": 0, "dur": 10, "pid": 1, "tid": 0},
        {"name": "i", "ph": "X", "ts": 2, "dur": 3, "pid": 1, "tid": 0},
        {"name": "n", "ph": "X", "ts": 20, "dur": 5, "pid": 1, "tid": 0},
    ]})


# ---------------------------------------------------------------------------
# overlap attribution
# ---------------------------------------------------------------------------

def test_interval_algebra():
    u = interval_union([(3.0, 4.0), (0.0, 2.0), (1.0, 2.5), (4.0, 5.0)])
    assert u == [(0.0, 2.5), (3.0, 5.0)]
    assert interval_total(u) == pytest.approx(4.5)
    assert interval_subtract([(0.0, 10.0)], [(2.0, 3.0), (5.0, 7.0)]) \
        == [(0.0, 2.0), (3.0, 5.0), (7.0, 10.0)]
    assert interval_subtract([(0.0, 2.0)], [(0.0, 3.0)]) == []


def _span(kind, lane, s, e):
    return Span(name=kind, track=lane, start=s, end=e, cat="task",
                args=(("kind", kind), ("lane", lane)))


def test_executed_exposed_comm_synthetic():
    spans = [
        _span("ATTN", "AG", 0.0, 2.0),
        _span("A2E", "A2E", 1.0, 3.0),   # 1s beyond AG -> exposed 1s
        _span("EXP", "EG", 3.0, 5.0),
        _span("E2A", "E2A", 4.0, 7.0),   # 2s beyond EG -> exposed 2s
    ]
    exp = executed_exposed_comm(spans)
    assert exp["A2E"] == pytest.approx(1.0)
    assert exp["E2A"] == pytest.approx(2.0)
    assert exp["total"] == pytest.approx(3.0)


def test_attribute_overlap_on_exact_schedule_spans():
    """Feeding the scheduler's own (task, start, end) spans back through
    the attributor must produce gap == 0: both sides reduce the same
    intervals."""
    graph = lower(_Plan, LoweringSpec(T=2))
    res = schedule(graph, _costs())
    spans = [Span(name=t.kind, track=t.resource, start=s, end=e,
                  cat="task", args=(("kind", t.kind),
                                    ("lane", t.resource)))
             for t, s, e in res.spans()]
    rep = attribute_overlap(spans, res)
    assert rep.gap == pytest.approx(0.0, abs=1e-12)
    assert rep.makespan_executed == pytest.approx(res.makespan)
    for lane, busy in res.busy.items():
        assert rep.busy_executed.get(lane, 0.0) == pytest.approx(busy)
    ex = rep.breakdown_executed
    md = rep.breakdown_modeled.as_dict()
    for cls in ("gemm", "attn", "comm"):
        assert ex[cls] == pytest.approx(md[cls])
    d = rep.as_dict()
    assert d["gap"] == rep.gap
    assert d["busy_modeled_AG_s"] == pytest.approx(res.busy["AG"])


def test_schedule_result_spans_and_lane_idle():
    graph = lower(_Plan, LoweringSpec(T=1))
    res = schedule(graph, _costs())
    spans = res.spans()
    assert len(spans) == len(graph.tasks)
    assert all(e >= s for _, s, e in spans)
    idle = res.lane_idle()
    for lane, busy in res.busy.items():
        assert idle[lane] == pytest.approx(res.makespan - busy)


# ---------------------------------------------------------------------------
# engine wiring
# ---------------------------------------------------------------------------

def _mini_engine(**kw):
    from repro.configs import get_smoke_config
    from repro.runtime.engine import ServingEngine
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    return ServingEngine(cfg, num_slots=2, max_context=64, **kw)


def _serve(eng, n=2, max_new=3):
    from repro.runtime.request import Request
    rng = np.random.RandomState(0)
    reqs = [Request(prompt=list(rng.randint(1, 100, size=4 + i)),
                    max_new_tokens=max_new) for i in range(n)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return reqs


@pytest.mark.slow
def test_tracer_off_is_bit_identical_and_compiles_nothing_new():
    """The acceptance lock: tracing changes neither the decoded tokens
    nor the set of compiled decode programs."""
    eng_off = _mini_engine(seed=3)
    eng_on = _mini_engine(seed=3, tracer=TraceRecorder())
    reqs_off = _serve(eng_off)
    reqs_on = _serve(eng_on)
    assert [r.output for r in reqs_off] == [r.output for r in reqs_on]
    assert eng_off._decode_jit._cache_size() \
        == eng_on._decode_jit._cache_size()
    assert len(eng_on.tracer.by_cat("phase")) > 0
    queued = [s for s in eng_on.tracer.by_cat("request")
              if s.name == "queued"]
    assert len(queued) == len(reqs_on)
    eng_off.close(); eng_on.close()


@pytest.mark.slow
def test_engine_metrics_and_registry_reset():
    eng = _mini_engine()
    reqs = _serve(eng)
    m = eng.metrics
    snap = m.snapshot()
    assert snap["repro_engine_decode_step_seconds_count"] >= 1
    assert snap["repro_engine_steps_total"] == float(eng.stats.steps)
    finished = snap['repro_engine_requests_total{state="finished"}']
    assert finished == float(len(reqs))
    assert m.histogram("repro_engine_ttft_seconds").count == len(reqs)
    assert m.histogram("repro_engine_tpot_seconds").count == len(reqs)
    # prometheus text parses and carries the histogram family
    names = {n for n, _, _ in parse_prometheus(m.render_prometheus())}
    assert "repro_engine_ttft_seconds_bucket" in names
    # seed telemetry with an EWMA, then check ONE reset clears all of it
    assert eng.telemetry.phases
    eng.reset_stats()
    assert eng.stats.steps == 0
    assert not eng.telemetry.phases and not eng.telemetry.keys
    assert m.histogram("repro_engine_ttft_seconds").count == 0
    assert m.snapshot()["repro_engine_decode_step_seconds_count"] == 0
    eng.close()


def test_engine_metrics_false_disables():
    eng = _mini_engine(metrics=False)
    assert eng.metrics is None
    eng.reset_stats()       # still resets the direct surfaces
    assert eng.stats.steps == 0
    eng.close()


def test_step_timer_reset_clears_ewma_state():
    from repro.profiling.telemetry import StepTimer
    t = StepTimer(key_warmup=0)
    for _ in range(3):
        t.observe("decode", 2e-3, predicted_s=1e-3, key="k")
    assert t.key_residual("k") is not None
    assert t.snapshot()["decode_count"] == 3
    t.reset()
    assert not t.phases and not t.keys
    assert t.snapshot()["tracked_keys"] == 0


def test_paging_stats_and_tracker_reset():
    from repro.placement.tracker import ExpertLoadTracker
    from repro.runtime.paging import PagingStats
    ps = PagingStats(prefix_hit_tokens=5, prefix_miss_tokens=5,
                     preemptions=2)
    ps.reset()
    assert ps.prefix_hit_rate == 0.0 and ps.preemptions == 0
    tr = ExpertLoadTracker(4)
    tr.observe([4.0, 0.0, 0.0, 0.0])
    assert tr.snapshot()["imbalance"] == pytest.approx(4.0)
    tr.reset()
    assert tr.snapshot()["observations"] == 0.0
