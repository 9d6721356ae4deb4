"""Host-side span tracing on a monotonic clock.

A ``Span`` is (name, track, cat, start, end, args); a ``TraceRecorder``
is an append-only list of them plus the clock that stamps them. Three
span families (taxonomy table in DESIGN.md):

  * ``cat="phase"``   engine step phases (admit / prefill_chunk /
                      decode_step), opened by the engine's host loop
                      around its already-fenced device calls — real
                      wall-clock intervals;
  * ``cat="task"``    one span per task (ATTN/SHARED/GATE/A2E/EXP/E2A/
                      REP), tagged kind/layer/mb/chunk/lane. The DEP
                      executor's walk records the op-*emission* of each
                      task (``args["emit"]=True`` — trace-time, once per
                      compiled program: the executed program order, not
                      durations);
  * ``cat="request"`` request lifecycle segments (queued / prefill /
                      decode) reconstructed from the request's
                      timestamps when it finishes — TTFT/TPOT live here.

The *active tracer* is a context variable: the engine scopes it around
its model calls (``use_tracer``), and ``core.dep``'s walker asks
``active_tracer()`` per walk with zero coupling to the engine. With no
tracer set (or a disabled one) every hook is None/no-op and the executor
emits the exact same ops — tracing off compiles the identical program
(test-locked).

Every ``span`` also enters ``jax.profiler.TraceAnnotation("<track>.<name>")``
so, under a running profiler, the same interval sits on the host plane of
the profiler's trace, on the device trace's clock. Two hooks add spans the
host loop does not open itself: ``watch_gc`` records each Python
collection (``gc``, with its generation), and ``watch_compiles`` records
each backend compile (``compile``) while a tracer is scoped.
"""
from __future__ import annotations

import gc
import time
import weakref
from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax


@dataclass(frozen=True)
class Span:
    """One traced interval. ``start``/``end`` are seconds on the
    recorder's clock (``end == start`` for instant events)."""

    name: str
    track: str
    start: float
    end: float
    cat: str = "phase"
    args: Tuple[Tuple[str, Any], ...] = ()

    @property
    def duration(self) -> float:
        return self.end - self.start

    def arg(self, key: str, default=None):
        for k, v in self.args:
            if k == key:
                return v
        return default


class TraceRecorder:
    """Append-only span sink on one monotonic clock.

    ``enabled=False`` turns every hook into a no-op (the engine keeps the
    object wired so flipping tracing on needs no re-plumbing).
    ``origin`` is the construction timestamp exports are made relative
    to, so multiple recorders/export groups align.
    """

    def __init__(self, enabled: bool = True, clock=time.perf_counter):
        self.enabled = enabled
        self.clock = clock
        self.origin = clock()
        self.spans: List[Span] = []
        self._gc_hook = None
        self._gc_open = None

    # -- recording ------------------------------------------------------
    def add_span(self, name: str, track: str, start: float, end: float,
                 cat: str = "phase", **args) -> None:
        if not self.enabled:
            return
        self.spans.append(Span(name=name, track=track, start=start,
                               end=end, cat=cat,
                               args=tuple(sorted(args.items()))))

    @contextmanager
    def span(self, name: str, track: str = "engine", cat: str = "phase",
             **args):
        """Time a block: records one span on exit (even on error), and
        marks it on the profiler's trace as ``<track>.<name>``."""
        if not self.enabled:
            yield self
            return
        t0 = self.clock()
        try:
            with jax.profiler.TraceAnnotation(f"{track}.{name}"):
                yield self
        finally:
            self.add_span(name, track, t0, self.clock(), cat=cat, **args)

    def instant(self, name: str, track: str = "engine",
                cat: str = "instant", **args) -> None:
        if not self.enabled:
            return
        t = self.clock()
        self.add_span(name, track, t, t, cat=cat, **args)

    def task_span(self, task, start: float, end: float,
                  emit: bool = False, **args) -> None:
        """One span for an executed (or emitted) IR task, tagged with the
        graph coordinates the overlap attributor groups by."""
        self.add_span(task.kind, task.resource, start, end, cat="task",
                      kind=task.kind, layer=task.layer, mb=task.mb,
                      chunk=task.chunk, lane=task.resource, emit=emit,
                      **args)

    def request_lifecycle(self, req, finish_t: Optional[float] = None
                          ) -> None:
        """Record a finished request's lifecycle segments from its
        timestamps: queued (submit -> admit), prefill (admit -> first
        token), decode (first token -> finish). Missing stamps collapse
        their segment."""
        if not self.enabled:
            return
        finish = finish_t if finish_t is not None else \
            (req.finish_t if req.finish_t is not None else self.clock())
        track = f"req-{req.request_id}"
        admit = req.admit_t if getattr(req, "admit_t", None) is not None \
            else finish
        first = req.first_token_t if req.first_token_t is not None \
            else finish
        rid = req.request_id
        state = getattr(req.state, "value", str(req.state))
        self.add_span("queued", track, req.arrival_t, admit,
                      cat="request", request_id=rid, state=state)
        if admit < first:
            self.add_span("prefill", track, admit, first, cat="request",
                          request_id=rid, state=state)
        if first < finish:
            self.add_span("decode", track, first, finish, cat="request",
                          request_id=rid, state=state,
                          tokens=len(req.output))

    # -- readers --------------------------------------------------------
    def task_spans(self, emitted: Optional[bool] = None) -> List[Span]:
        """Task-category spans; ``emitted`` filters trace-time emission
        records (True), executed spans (False), or returns both (None)."""
        out = [s for s in self.spans if s.cat == "task"]
        if emitted is None:
            return out
        return [s for s in out if bool(s.arg("emit")) == emitted]

    def by_cat(self, cat: str) -> List[Span]:
        return [s for s in self.spans if s.cat == cat]

    def clear(self) -> None:
        self.spans = []
        self.origin = self.clock()

    # -- host events outside the loop's own spans -----------------------
    def watch_gc(self) -> None:
        """Record each Python garbage collection as a ``gc`` phase span
        (``generation`` arg), also marked on the profiler's trace, until
        ``unwatch_gc`` or until this recorder is collected (the hook holds
        it weakly)."""
        if not self.enabled or self._gc_hook is not None:
            return
        on_gc = weakref.WeakMethod(self._on_gc)

        def hook(phase, info):
            method = on_gc()
            if method is not None:
                method(phase, info)
        self._gc_hook = weakref.finalize(self, _unhook, hook)
        gc.callbacks.append(hook)

    def unwatch_gc(self) -> None:
        if self._gc_hook is not None:
            self._gc_hook()
            self._gc_hook = None
        self._gc_open = None

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            mark = jax.profiler.TraceAnnotation("engine.gc")
            mark.__enter__()
            self._gc_open = (self.clock(), mark)
        elif self._gc_open is not None:
            t0, mark = self._gc_open
            self._gc_open = None
            mark.__exit__(None, None, None)
            self.add_span("gc", "engine", t0, self.clock(),
                          generation=info.get("generation"))

    def __len__(self) -> int:
        return len(self.spans)

    def __repr__(self) -> str:
        cats: Dict[str, int] = {}
        for s in self.spans:
            cats[s.cat] = cats.get(s.cat, 0) + 1
        body = ", ".join(f"{k}={v}" for k, v in sorted(cats.items()))
        state = "on" if self.enabled else "off"
        return f"TraceRecorder({state}; {body or 'empty'})"


# ---------------------------------------------------------------------------
# active-tracer scoping (how the executor finds the engine's recorder)
# ---------------------------------------------------------------------------

_ACTIVE: ContextVar[Optional[TraceRecorder]] = ContextVar(
    "repro_obs_active_tracer", default=None)


def active_tracer() -> Optional[TraceRecorder]:
    """The recorder scoped by the innermost ``use_tracer`` (None when
    none is scoped or it is disabled) — what ``core.dep``'s task walk
    consults. Must stay cheap: it runs once per executor walk."""
    t = _ACTIVE.get()
    return t if (t is not None and t.enabled) else None


def _unhook(hook) -> None:
    if hook in gc.callbacks:
        gc.callbacks.remove(hook)


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_watching_compiles = False


def _on_compile(event: str, duration: float, **kw) -> None:
    tracer = active_tracer()
    if event == _COMPILE_EVENT and tracer is not None:
        now = tracer.clock()
        tracer.add_span("compile", "engine", now - duration, now)


def watch_compiles() -> None:
    """Record each backend compile (or persistent-cache load) made while
    a tracer is scoped as a ``compile`` phase span ending when JAX reports
    it. Registers one listener a process; without a scoped tracer it
    records nothing."""
    global _watching_compiles
    if not _watching_compiles:
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _watching_compiles = True


@contextmanager
def use_tracer(tracer: Optional[TraceRecorder]):
    """Scope ``tracer`` as the active tracer for the block (None scopes
    tracing OFF, shadowing any outer tracer)."""
    token = _ACTIVE.set(tracer)
    try:
        yield tracer
    finally:
        _ACTIVE.reset(token)
