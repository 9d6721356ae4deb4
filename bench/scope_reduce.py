"""Reduce a JAX profiler trace to what the program itself marks on it:
the device time of its named scopes, and the device's busy time inside
its own host spans. A companion of ``trace_reduce.py``, on the same
traced window (the ``bench.traced`` mark).

* A scope's time is the summed duration of the operations of one program
  (the decode step's, ``jit(_decode_step)``) whose scope path holds the
  scope's name as whole path components. The path is the stat
  ``SCOPE_STAT`` of the operation's event metadata
  (``jit(_decode_step)/moe/expert_ffn/ecm,emh->ech/dot_general``), which
  ``ProfileData`` does not expose: ``scope_paths`` reads it from the
  serialized XSpace.
* Busy time inside a host span: the program marks its spans on the
  profiler's trace (``engine.<name>`` annotations on a host plane); the
  part of their union in which some operation ran, averaged over the
  devices. No alignment is needed: the marks are on the profiler's clock.

A run of ``harness.py`` deletes its trace before the metric readers run
and keeps only ``trace_reduce.Reduction``, so no metric reads these
numbers yet; ``reduce_file`` reads a trace kept by hand.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

from trace_reduce import OPS_LINE, _clip, host_marks, union

#: the op stat that holds an operation's scope path
SCOPE_STAT = "tf_op"
#: the program whose operations' scopes are read
SCOPE_PROGRAM = "jit(_decode_step)"
#: the named scopes read, as the program names them
SCOPES = ("attention", "moe/router", "moe/dispatch", "moe/expert_ffn",
          "moe/combine", "moe/shared_expert", "lm_head", "sampler")
#: prefix of the program's own span marks on a host plane
SPAN_MARK = "engine."


@dataclass
class ScopeReduction:
    scope_s: Dict[str, float]       # scope -> device time, per device
    span_s: Dict[str, float]        # mark -> length of the marks' union
    span_busy_s: Dict[str, float]   # mark -> busy inside it, per device


def _space_class():
    """A message class that reads, of a serialized XSpace, each plane's
    name and its event and stat metadata (field numbers of
    ``tsl/profiler/protobuf/xplane.proto``); every other field, the
    events included, is skipped."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xspace.proto",
                                            package="bench_xspace")
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED

    def message(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, number, ftype, label, tname in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=label)
            if tname:
                f.type_name = ".bench_xspace." + tname

    message("Stat", ("metadata_id", 1, F.TYPE_INT64, one, None),
            ("str_value", 5, F.TYPE_STRING, one, None),
            ("ref_value", 7, F.TYPE_UINT64, one, None))
    message("EventMetadata", ("name", 2, F.TYPE_STRING, one, None),
            ("stats", 5, F.TYPE_MESSAGE, many, "Stat"))
    message("StatMetadata", ("name", 2, F.TYPE_STRING, one, None))
    # a map is a repeated message of (key = 1, value = 2)
    message("EventMetadataEntry", ("key", 1, F.TYPE_INT64, one, None),
            ("value", 2, F.TYPE_MESSAGE, one, "EventMetadata"))
    message("StatMetadataEntry", ("key", 1, F.TYPE_INT64, one, None),
            ("value", 2, F.TYPE_MESSAGE, one, "StatMetadata"))
    message("Plane", ("name", 2, F.TYPE_STRING, one, None),
            ("event_metadata", 4, F.TYPE_MESSAGE, many,
             "EventMetadataEntry"),
            ("stat_metadata", 5, F.TYPE_MESSAGE, many, "StatMetadataEntry"))
    message("Space", ("planes", 1, F.TYPE_MESSAGE, many, "Plane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xspace.Space"))


def scope_paths(serialized: bytes) -> Dict[str, Dict[str, str]]:
    """Per device plane, the scope path (``SCOPE_STAT``) of each operation
    name that has one; distinct paths of one name are joined by ``;``."""
    space = _space_class()()
    space.ParseFromString(serialized)
    out: Dict[str, Dict[str, str]] = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        keys = {k for k, n in names.items() if n == SCOPE_STAT}
        paths: Dict[str, set] = defaultdict(set)
        for e in plane.event_metadata:
            for st in e.value.stats:
                if st.metadata_id in keys:
                    paths[e.value.name].add(
                        st.str_value or names.get(st.ref_value, ""))
        out[plane.name] = {n: ";".join(sorted(p)) for n, p in paths.items()}
    return out


def in_scope(path: str, scope: str) -> bool:
    """Whether a scope path (several may be joined by ``;``) names an
    operation of ``SCOPE_PROGRAM`` under ``scope``: the scope's components
    appear whole, in order, after the program's."""
    n = len(SCOPE_PROGRAM)
    return any(p.startswith(SCOPE_PROGRAM + "/") and f"/{scope}/" in p[n:]
               for p in path.split(";"))


def overlap(a: Sequence[Tuple[float, float]],
            b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def span_marks(planes, lo: float, hi: float
               ) -> Dict[str, List[Tuple[float, float]]]:
    """The program's own span marks (``engine.<name>``) on every host
    line: per name, the union of their intervals inside [lo, hi)."""
    marks: Dict[str, list] = defaultdict(list)
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_MARK):
                    marks[e.name].append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return {n: _clip(union(iv), lo, hi) for n, iv in marks.items()}


def reduce_planes(planes, paths: Dict[str, Dict[str, str]]
                  ) -> ScopeReduction:
    """``planes``: a ``jax.profiler.ProfileData``'s planes; ``paths``: the
    operations' scope paths (``scope_paths``). ``scope_s`` holds each of
    ``SCOPES`` that some operation has, ``span_s`` each mark there is:
    a program without them reads nothing, not a zero."""
    planes = list(planes)
    windows, _ = host_marks(planes)
    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    if not windows:
        raise ValueError("no bench.traced mark on a host line")
    if not devices:
        raise ValueError("no /device:TPU:<n> plane in the trace")
    lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
    marks = span_marks(planes, lo, hi)
    scope_ns: Dict[str, float] = defaultdict(float)
    busy_ns: Dict[str, float] = defaultdict(float)
    for plane in devices:
        ivs = []
        plane_paths = paths.get(plane.name, {})
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                a, b = e.start_ns, e.start_ns + e.duration_ns
                if b <= lo or a >= hi:
                    continue
                a, b = max(a, lo), min(b, hi)
                ivs.append((a, b))
                path = plane_paths.get(e.name)
                for sc in SCOPES if path else ():
                    if in_scope(path, sc):
                        scope_ns[sc] += b - a
        u = union(ivs)
        for name, iv in marks.items():
            busy_ns[name] += overlap(u, iv)
    n = len(devices)
    return ScopeReduction(
        scope_s={sc: t / 1e9 / n for sc, t in scope_ns.items()},
        span_s={m: sum(b - a for a, b in iv) / 1e9
                for m, iv in marks.items()},
        span_busy_s={m: busy_ns[m] / 1e9 / n for m in marks})


def load_text(text: str):
    """(planes, scope paths) of an XSpace in text form."""
    from jax.profiler import ProfileData
    return _load(ProfileData.text_proto_to_serialized_xspace(text))


def _load(serialized: bytes):
    from jax.profiler import ProfileData
    planes = ProfileData.from_serialized_xspace(serialized).planes
    return list(planes), scope_paths(serialized)


def reduce_file(path: str) -> ScopeReduction:
    """Reduce an ``.xplane.pb`` file, or an XSpace in text form
    (``.pbtxt``)."""
    if path.endswith(".pbtxt"):
        with open(path) as f:
            return reduce_planes(*load_text(f.read()))
    with open(path, "rb") as f:
        return reduce_planes(*_load(f.read()))
