"""The engine's own measurement: phase spans finer than the step (also
written to the profiler's trace), garbage-collection and compile spans,
named scopes in the jitted decode step, and the expert-row counters."""
import contextlib
import dataclasses
import gc
import glob
import re

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.obs.trace import TraceRecorder
from repro.runtime.engine import ServingEngine
from repro.runtime.request import Request

SCOPES = ("attention", "moe/router", "moe/dispatch", "moe/expert_ffn",
          "moe/combine", "moe/shared_expert", "lm_head", "sampler")
#: child phase span -> the phase span it runs inside
PARENT = {"schedule": "admit", "prefill_chunk": "admit",
          "kv_merge": "admit", "prefill_dispatch": "prefill_chunk",
          "prefill_wait": "prefill_chunk",
          "decode_dispatch": "decode_step", "decode_wait": "decode_step"}


def _engine(**kw):
    # qwen2-moe smoke: 2 MoE layers of 4 experts, top-2, capacity factor
    # 1.25, a shared expert
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    return ServingEngine(cfg, num_slots=2, max_context=64, seed=3, **kw)


def _serve(eng, greedy=True):
    """Two prompts of 5 and 7 tokens (one prefill group, bucket 64),
    three new tokens each; returns their outputs."""
    rng = np.random.RandomState(0)
    reqs = [Request(prompt=list(rng.randint(1, 100, size=n)),
                    max_new_tokens=3,
                    temperature=0.0 if greedy else 0.7,
                    top_k=0 if greedy else 3) for n in (5, 7)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return [r.output for r in reqs]


def _decode_hlo(eng) -> str:
    """The compiled decode step (top-k sampling on) as HLO text."""
    eng.kv.ensure_caches()
    lengths = jax.numpy.zeros((eng.num_slots,), jax.numpy.int32)
    return eng._decode_jit.lower(
        eng.params, eng.last_tokens, eng.kv.caches, eng.temps, eng.top_ks,
        eng.key, lengths, None, use_topk=True).compile().as_text()


def _scopes_in(hlo: str):
    names = set(re.findall(r'op_name="([^"]+)"', hlo))
    return {s for s in SCOPES if any(f"/{s}/" in n for n in names)}


def test_decode_step_scopes_in_op_metadata_and_tokens_unchanged(
        monkeypatch):
    eng = _engine()
    assert _scopes_in(_decode_hlo(eng)) == set(SCOPES)
    scoped = _serve(eng)
    eng.close()
    # the same engine with every named scope made a no-op
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _engine()
    assert _scopes_in(_decode_hlo(bare)) == set()
    assert _serve(bare) == scoped
    bare.close()


def test_child_spans_nest_in_their_parents():
    eng = _engine(tracer=TraceRecorder())
    _serve(eng, greedy=False)
    spans = eng.tracer.by_cat("phase")
    names = {s.name for s in spans}
    assert set(PARENT) | {"plan", "emit", "gc"} <= names
    for child in spans:
        parent = PARENT.get(child.name)
        if parent is None:
            continue
        assert any(p.name == parent and p.start <= child.start
                   and child.end <= p.end for p in spans), child
    eng.close()


def test_step_spans_on_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData
    eng = _engine(tracer=TraceRecorder())
    _serve(eng)                                   # compiles outside
    jax.profiler.start_trace(str(tmp_path))
    _serve(eng)
    jax.profiler.stop_trace()
    eng.close()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = [e.name for p in ProfileData.from_file(path[0]).planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events]
    for name in ("admit", "schedule", "plan", "decode_step",
                 "decode_dispatch", "decode_wait", "emit",
                 "prefill_chunk", "kv_merge"):
        assert f"engine.{name}" in host, name


def test_no_tracer_enters_no_annotation_and_no_gc_hook(monkeypatch):
    entered = []

    class Mark:
        def __init__(self, name, **kw):
            entered.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Mark)
    hooks = list(gc.callbacks)
    eng = _engine()
    _serve(eng)
    assert entered == [] and gc.callbacks == hooks
    eng.tracer = TraceRecorder()                  # set: hook and marks
    assert len(gc.callbacks) == len(hooks) + 1
    _serve(eng)
    gc.collect()
    assert "engine.decode_step" in entered and "engine.gc" in entered
    gens = {s.arg("generation") for s in eng.tracer.spans
            if s.name == "gc"}
    assert 2 in gens
    eng.tracer = None                             # unset: hook removed
    assert gc.callbacks == hooks
    eng.close()


def test_compile_spans_only_while_a_tracer_is_scoped():
    eng = _engine(tracer=TraceRecorder())
    _serve(eng)
    compiles = [s for s in eng.tracer.spans if s.name == "compile"]
    assert compiles and all(s.end >= s.start for s in compiles)
    # a compile outside the engine's scopes records nothing
    n = len(eng.tracer.spans)
    jax.jit(lambda x: x * 3 + 1)(np.float32(2.0)).block_until_ready()
    assert not [s for s in eng.tracer.spans[n:] if s.name == "compile"]
    eng.close()


@pytest.mark.parametrize("impl", ["capacity", "dense"])
def test_expert_row_counters_match_a_hand_count(impl):
    eng = _engine()
    eng.model.ctx = dataclasses.replace(eng.model.ctx, moe_impl=impl)
    _serve(eng)
    assert eng.stats.steps == 3                   # both rows live in each
    m = eng.metrics

    def count(kind, phase):
        return m.counter(f"repro_engine_expert_rows_{kind}_total",
                         labels={"phase": phase}).value

    # 2 MoE layers x 4 experts. Prefill: one group of 2 rows x bucket 64
    # (128 tokens); capacity ceil(128 * 2 / 4 * 1.25) = 80. Decode: 2
    # slots a step; capacity ceil(2 * 2 / 4 * 1.25) = 2. Dense: every
    # expert on every token.
    if impl == "capacity":
        assert count("computed", "prefill") == 2 * 4 * 80
        assert count("computed", "decode") == 3 * 2 * 4 * 2
    else:
        assert count("computed", "prefill") == 2 * 4 * 128
        assert count("computed", "decode") == 3 * 2 * 4 * 2
    # routed: unpadded prompt tokens (4 + 6; the last goes to decode) and
    # live decode rows, x top-2 x 2 layers
    assert count("routed", "prefill") == (4 + 6) * 2 * 2
    assert count("routed", "decode") == 3 * 2 * 2 * 2
    eng.reset_stats()
    assert count("computed", "prefill") == 0
    eng.close()


def test_gc_hook_goes_with_its_recorder():
    hooks = list(gc.callbacks)
    rec = TraceRecorder()
    rec.watch_gc()
    rec.watch_gc()                                # once, however often
    assert len(gc.callbacks) == len(hooks) + 1
    gc.collect()
    assert [s.name for s in rec.spans] == ["gc"]
    del rec                                       # never unwatched
    gc.collect()
    assert gc.callbacks == hooks
