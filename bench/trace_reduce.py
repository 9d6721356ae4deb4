"""Reduce a JAX profiler trace (an XSpace: ``*.xplane.pb``) to the device
numbers the per-layer metrics read.

* Device planes are those named ``/device:TPU:<n>``; their operations are
  the events of the line ``XLA Ops``.
* Busy time is the union of those events' intervals inside the traced
  window, averaged over the devices; idle is the rest of the window.
* A kernel's time is the summed duration of the operations that are the
  kernel's own calls: an event's name may be the whole HLO instruction
  (``%decode_attention.3 = bf16[...] custom-call(...)``), and the name of
  the instruction, before `` = ``, is the kernel's name or that name with
  a ``.<n>`` suffix. An operation that only takes the kernel's output
  (``fusion(%decode_attention.3)``) is not the kernel.
* The traced window and the host's phases come from the host plane: the
  harness marks the window with a ``TraceAnnotation`` and each engine step
  with another, and the engine's own phase spans (``admit``,
  ``prefill_chunk``, ``decode_step``, on the host's ``perf_counter``) are
  put on the profiler's clock through the step marks.
* Each idle gap is named after what the host was doing at its middle: the
  innermost engine phase, ``step_other`` inside a step but outside every
  phase, or ``between_steps``.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

WINDOW_MARK = "bench.traced"
STEP_MARK = "bench.step"
OPS_LINE = "XLA Ops"


@dataclass
class Reduction:
    window_s: float
    busy_s: float                   # averaged over the device planes
    devices: int
    kernel_s: Dict[str, float]      # kernel name -> summed device time
    kernel_count: Dict[str, int]
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    idle_by_phase: Dict[str, float] = field(default_factory=dict)


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {directory}")
    return paths[-1]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def instruction(event_name: str) -> str:
    """The HLO instruction's own name in an operation event's name: the
    text before `` = ``, without the leading ``%``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def is_kernel(event_name: str, kernel: str) -> bool:
    """Whether the operation is a call of ``kernel`` (named ``kernel`` or
    ``kernel.<n>``)."""
    op = instruction(event_name)
    return op == kernel or (op.startswith(kernel + ".")
                            and op[len(kernel) + 1:].isdigit())


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def host_marks(planes) -> Tuple[List[Tuple[float, float]],
                                List[Tuple[float, float]]]:
    """(window marks, step marks) as (start_ns, end_ns), from every host
    line."""
    windows, steps = [], []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, a, b in _events(line):
                if name == WINDOW_MARK:
                    windows.append((a, b))
                elif name == STEP_MARK:
                    steps.append((a, b))
    return windows, sorted(steps)


def align_phases(step_marks, step_host_starts, phases):
    """Map engine phase spans [(name, start_s, end_s)] on the host clock
    onto the profiler's clock (ns): the offset is the median over the
    steps of (mark start - host start); both lists hold the traced steps
    in order."""
    n = min(len(step_marks), len(step_host_starts))
    if n == 0:
        return []
    offs = sorted(step_marks[i][0] - step_host_starts[i] * 1e9
                  for i in range(n))
    off = offs[n // 2]
    return [(name, a * 1e9 + off, b * 1e9 + off) for name, a, b in phases]


def _phase_at(t, phases, steps):
    """The innermost phase holding time ``t`` (shortest span wins)."""
    best = None
    for name, a, b in phases:
        if a <= t <= b and (best is None or b - a < best[1]):
            best = (name, b - a)
    if best is not None:
        return best[0]
    i = bisect.bisect_right(steps, (t, float("inf"))) - 1
    if i >= 0 and steps[i][0] <= t <= steps[i][1]:
        return "step_other"
    return "between_steps"


def reduce_planes(planes, kernels: Sequence[str] = ("decode_attention",),
                  phases: Sequence[Tuple[str, float, float]] = (),
                  step_host_starts: Sequence[float] = (),
                  top: int = 10) -> Reduction:
    """``planes``: a ``jax.profiler.ProfileData``'s planes. ``phases``:
    the engine's phase spans on the host clock (seconds);
    ``step_host_starts``: the host-clock start of each traced step, in
    order, matching the step marks."""
    planes = list(planes)
    windows, steps = host_marks(planes)
    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    if not windows:
        raise ValueError(f"no {WINDOW_MARK!r} mark on a host line")
    if not devices:
        raise ValueError("no /device:TPU:<n> plane in the trace")
    lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
    window_ns = hi - lo
    busy = []
    kernel_ns: Dict[str, float] = defaultdict(float)
    kernel_n: Dict[str, int] = defaultdict(int)
    op_ns: Dict[str, float] = defaultdict(float)
    gaps_all = []
    ph = align_phases(steps, list(step_host_starts), list(phases))
    for plane in devices:
        ivs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for name, a, b in _events(line):
                if b <= lo or a >= hi:
                    continue
                a, b = max(a, lo), min(b, hi)
                ivs.append((a, b))
                op_ns[name] += b - a
                for k in kernels:
                    if is_kernel(name, k):
                        kernel_ns[k] += b - a
                        kernel_n[k] += 1
        u = _clip(union(ivs), lo, hi)
        busy.append(sum(b - a for a, b in u))
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps_all.append((_phase_at((a + b) / 2, ph, steps), b - a))
    by_phase: Dict[str, float] = defaultdict(float)
    for name, d in gaps_all:
        by_phase[name] += d / 1e9 / len(devices)
    gaps_all.sort(key=lambda g: -g[1])
    tops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    return Reduction(
        window_s=window_ns / 1e9,
        busy_s=sum(busy) / len(devices) / 1e9,
        devices=len(devices),
        kernel_s={k: kernel_ns[k] / 1e9 / len(devices) for k in kernels},
        kernel_count={k: kernel_n[k] for k in kernels},
        top_ops=[(n, d / 1e9 / len(devices)) for n, d in tops],
        idle_gaps=[(n, d / 1e9) for n, d in gaps_all[:top]],
        idle_by_phase=dict(by_phase))


def _quote(text: str) -> str:
    """``text`` as the body of a text-proto string."""
    return text.replace("\\", "\\\\").replace('"', '\\"')


def excerpt(planes, lo_ns: float, hi_ns: float) -> str:
    """The events of the device planes' ``XLA Ops`` lines and the host
    marks that overlap [lo_ns, hi_ns), clipped to it, as a text XSpace
    with times from ``lo_ns``: a small recorded trace for a test of this
    reduction."""
    out = []
    for pid, plane in enumerate(planes, 1):
        if plane.name.startswith("/device:TPU:"):
            keep = lambda line, name: line.name == OPS_LINE  # noqa: E731
        elif plane.name.startswith("/host:"):
            keep = lambda line, name: name in (WINDOW_MARK,  # noqa: E731
                                               STEP_MARK)
        else:
            continue
        names: Dict[str, int] = {}
        lines = []
        for line in plane.lines:
            evs = [(n, max(a, lo_ns), min(b, hi_ns))
                   for n, a, b in _events(line)
                   if keep(line, n) and b > lo_ns and a < hi_ns]
            if not evs:
                continue
            body = [f'  lines {{ id: {len(lines) + 1} name: "{line.name}"'
                    " timestamp_ns: 0"]
            for n, a, b in evs:
                i = names.setdefault(n, len(names) + 1)
                body.append(f"    events {{ metadata_id: {i} offset_ps: "
                            f"{round((a - lo_ns) * 1000)} duration_ps: "
                            f"{round((b - a) * 1000)} }}")
            lines.append("\n".join(body + ["  }"]))
        if not lines:
            continue
        meta = [f'  event_metadata {{ key: {i} value {{ id: {i} name: '
                f'"{_quote(n)}" }} }}' for n, i in names.items()]
        out.append("\n".join([f'planes {{ id: {pid} name: "{plane.name}"']
                             + lines + meta + ["}"]))
    return "\n".join(out) + "\n"


def reduce_file(path: str, **kw) -> Reduction:
    """Reduce an ``.xplane.pb`` file, or an XSpace in text form
    (``.pbtxt``)."""
    from jax.profiler import ProfileData
    if path.endswith(".pbtxt"):
        with open(path) as f:
            data = ProfileData.from_text_proto(f.read())
    else:
        data = ProfileData.from_file(path)
    return reduce_planes(data.planes, **kw)
