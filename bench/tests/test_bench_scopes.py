"""The reduction of what the program marks on a profiler trace (its named
scopes and its own span marks), on hand-made XSpaces and on a recorded
TPU v5e trace whose answers were counted by hand."""
import gzip
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import scope_reduce as sr  # noqa: E402
import trace_reduce as tr  # noqa: E402


def _xspace(device_events, host_events):
    """Text XSpace whose device operations carry a scope path in their
    event metadata, as a TPU trace has it: device events as (name,
    start_ns, duration_ns, path or None), host events as (name, start_ns,
    duration_ns)."""
    paths = {e[0]: e[3] for e in device_events}
    ids = {n: i + 1 for i, n in enumerate(sorted(paths))}
    dev = ['planes { id: 1 name: "/device:TPU:0"',
           '  lines { id: 1 name: "XLA Ops" timestamp_ns: 0']
    dev += [f"    events {{ metadata_id: {ids[n]} offset_ps: {s * 1000}"
            f" duration_ps: {d * 1000} }}" for n, s, d, _ in device_events]
    dev.append("  }")
    for n, i in ids.items():
        stat = (f' stats {{ metadata_id: 1 str_value: '
                f'"{tr._quote(paths[n])}" }}' if paths[n] else "")
        dev.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}"{stat} }} }}')
    dev.append(f'  stat_metadata {{ key: 1 value {{ id: 1 name: '
               f'"{sr.SCOPE_STAT}" }} }}')
    dev.append("}")
    hids = {n: i + 1 for i, n in enumerate(sorted({e[0] for e in
                                                    host_events}))}
    host = ['planes { id: 2 name: "/host:CPU"',
            '  lines { id: 1 name: "python" timestamp_ns: 0']
    host += [f"    events {{ metadata_id: {hids[n]} offset_ps: {s * 1000}"
             f" duration_ps: {d * 1000} }}" for n, s, d in host_events]
    host.append("  }")
    host += [f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}"'
             " } }" for n, i in hids.items()]
    host.append("}")
    return "\n".join(dev + host) + "\n"


def test_scope_time_and_busy_inside_span_marks():
    # window 0..1000 ns. Decode-program ops under moe/expert_ffn (one
    # path of two joined by ';'), the sampler, and a nested scope name
    # that is not a scope read; an eager op with the same scope name but
    # another program; an op with no path.
    P = sr.SCOPE_PROGRAM
    dev = [("fusion.1", 100, 50, f"{P}/moe/expert_ffn/dot_general"),
           ("fusion.2", 160, 40,
            f"{P}/moe/router/add;{P}/moe/expert_ffn/mul"),
           ("sort.3", 300, 30, f"{P}/sampler/sort"),
           ("fusion.4", 400, 20, f"{P}/xmoe/expert_ffn_x/add"),
           ("dot.5", 600, 100, "jit(dot_general)/moe/expert_ffn/dot"),
           ("copy.6", 800, 10, None),
           ("fusion.1", 990, 30, f"{P}/moe/expert_ffn/dot_general")]
    # two prefill marks, 550..750 and 780..880 (busy 600..700 and
    # 800..810), and one that starts before the window
    host = [("bench.traced", 0, 1000), ("engine.prefill_chunk", 550, 200),
            ("engine.prefill_chunk", 780, 100),
            ("engine.decode_step", -50, 250)]
    r = sr.reduce_planes(*sr.load_text(_xspace(dev, host)))
    assert r.scope_s == {"moe/expert_ffn": pytest.approx(100e-9),
                         "moe/router": pytest.approx(40e-9),
                         "sampler": pytest.approx(30e-9)}
    assert r.span_s["engine.prefill_chunk"] == pytest.approx(300e-9)
    assert r.span_busy_s["engine.prefill_chunk"] == \
        pytest.approx(110e-9)
    assert r.span_s["engine.decode_step"] == pytest.approx(200e-9)
    assert r.span_busy_s["engine.decode_step"] == pytest.approx(90e-9)
    # a trace without paths or marks (a program without the scopes and
    # spans) reads nothing, and raises nothing
    bare = sr.reduce_planes(*sr.load_text(_xspace(
        [(n, s, d, None) for n, s, d, _ in dev],
        [("bench.traced", 0, 1000)])))
    assert (bare.scope_s, bare.span_s, bare.span_busy_s) == ({}, {}, {})


@pytest.mark.parametrize("path, scope, inside", [
    ("jit(_decode_step)/moe/expert_ffn/dot_general", "moe/expert_ffn",
     True),
    ("jit(_decode_step)/jit(main)/moe/expert_ffn/jit(silu)/mul",
     "moe/expert_ffn", True),
    ("jit(_decode_step)/sampler", "sampler", False),
    ("jit(_decode_step)/attention/jit(decode_attention)/while",
     "attention", True),
    ("jit(_decode_step)/lm_head_x/dot", "lm_head", False),
    ("jit(prefill)/moe/expert_ffn/dot", "moe/expert_ffn", False),
    ("jit(_decode_step)/moe/router/top_k;jit(_decode_step)/sampler/sort",
     "sampler", True)])
def test_in_scope_reads_whole_components_of_the_program(path, scope,
                                                        inside):
    assert sr.in_scope(path, scope) is inside


def test_recorded_engine_spans_trace():
    """Three steps of qwen2moe.chat traced on a TPU v5e chip with the
    program's own marks: a decode step, a step that prefills one prompt
    and decodes, a decode step. Read by hand from the text: 54 operations
    whose metadata path lies under ``jit(_decode_step)/.../moe/expert_ffn/``
    (6 layers x 3 matrices x 3 steps) lasting 24,776,535 ns in all; 45
    under ``sampler`` lasting 11,324,248 ns; one ``engine.prefill_chunk``
    mark of 422,437,319 ns, inside which the union of the device's
    operations lasts 52,159,442 ns."""
    data = os.path.join(BENCH, "tests", "data", "v5e-engine-spans")
    with gzip.open(data + ".pbtxt.gz", "rt") as f:
        planes, paths = sr.load_text(f.read())
    with open(data + ".json") as f:
        host = json.load(f)
    r = sr.reduce_planes(planes, paths)
    assert r.scope_s["moe/expert_ffn"] == pytest.approx(24.776535e-3)
    assert r.scope_s["sampler"] == pytest.approx(11.324248e-3)
    assert r.span_s["engine.prefill_chunk"] == pytest.approx(0.422437319)
    assert r.span_busy_s["engine.prefill_chunk"] == \
        pytest.approx(0.052159442)
    # with the engine's finer phase spans, every idle gap of the existing
    # reduction is named after a span finer than the step
    red = tr.reduce_planes(planes, phases=host["phases"],
                           step_host_starts=host["step_host_starts"])
    assert red.idle_gaps and {n for n, _ in red.idle_gaps} <= {
        "schedule", "plan", "decode_dispatch", "decode_wait", "emit",
        "prefill_dispatch", "prefill_wait", "kv_merge", "gc", "compile"}
