"""The trace reduction, on a hand-made XSpace whose answers are known."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import trace_reduce as tr  # noqa: E402


def _xspace(device_events, host_events):
    """Text XSpace: events as (name, start_ns, duration_ns)."""
    def plane(pid, name, line, events):
        names = sorted({e[0] for e in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        out = [f'planes {{ id: {pid} name: "{name}"',
               f'  lines {{ id: 1 name: "{line}" timestamp_ns: 0']
        out += [f"    events {{ metadata_id: {ids[n]} offset_ps: {s * 1000}"
                f" duration_ps: {d * 1000} }}" for n, s, d in events]
        out.append("  }")
        out += [f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}"'
                f" }} }}" for n, i in ids.items()]
        out.append("}")
        return "\n".join(out)
    return (plane(1, "/device:TPU:0", "XLA Ops", device_events) + "\n"
            + plane(2, "/host:CPU", "python", host_events))


def _reduce(text, **kw):
    from jax.profiler import ProfileData
    return tr.reduce_planes(ProfileData.from_text_proto(text).planes, **kw)


def test_busy_union_kernels_and_gaps():
    # window 0..1000 ns; ops overlap at 100..300, a kernel at 500..600
    # (named by its whole HLO instruction, as a TPU trace names it) and an
    # operation that takes its output at 600..620, which is not the
    # kernel; one op straddles the window's end
    kernel = ("%decode_attention.7 = bf16[16,16,1,128] custom-call(%copy),"
              " custom_call_target=\\\"tpu_custom_call\\\"")
    consumer = ("%fusion.4 = bf16[16,16] fusion(%decode_attention.7),"
                " kind=kLoop")
    dev = [("fusion.1", 100, 150), ("fusion.2", 200, 100),
           (kernel, 500, 100), (consumer, 600, 20), ("fusion.1", 950, 200),
           ("fusion.3", 1200, 50)]
    host = [("bench.traced", 0, 1000), ("bench.step", 60, 590),
            ("bench.step", 700, 290)]
    # the host clock runs 60 ns behind the profiler's (median offset of
    # the two step marks, 50 and 60 ns)
    r = _reduce(_xspace(dev, host),
                phases=[("prefill_chunk", 0.3e-6, 0.45e-6)],
                step_host_starts=[0.0, 0.65e-6])
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx((200 + 120 + 50) * 1e-9)
    assert r.kernel_s["decode_attention"] == pytest.approx(100e-9)
    assert r.kernel_count["decode_attention"] == 1
    assert r.top_ops[0] == ("fusion.1", pytest.approx(200e-9))
    # gaps: 0-100 (its middle before the first step), 300-500 (in the
    # prefill phase, at 360..510 ns on the profiler's clock), 620-950 (its
    # middle in the second step, outside every phase)
    gaps = dict((round(d * 1e9), n) for n, d in r.idle_gaps)
    assert gaps == {100: "between_steps", 200: "prefill_chunk",
                    330: "step_other"}
    assert sum(r.idle_by_phase.values()) == pytest.approx(630e-9)


@pytest.mark.parametrize("name, kernel", [
    ("decode_attention", True), ("decode_attention.12", True),
    ("%decode_attention.3 = bf16[16,16,1,128]{3,2,1,0} custom-call(%a)",
     True),
    ("%fusion.5 = bf16[16,16]{1,0} fusion(%decode_attention.3)", False),
    ("%decode_attention_fusion.2 = bf16[16] fusion(%x)", False),
    ("%copy.9 = bf16[4] copy(%decode_attention)", False)])
def test_kernel_is_the_instruction_not_its_consumer(name, kernel):
    assert tr.is_kernel(name, "decode_attention") is kernel


def test_refuses_a_trace_without_a_device():
    text = _xspace([("fusion.1", 0, 10)], [("bench.traced", 0, 1000)])
    with pytest.raises(ValueError, match="device"):
        _reduce(text.replace("/device:TPU:0", "/device:CPU:0"))


def test_recorded_chip_trace():
    """Three decode steps of qwen2moe.chat traced on a TPU v5e chip
    (``run.py --trace 1 --keep-trace``; names repaired to valid text
    proto). Read by hand: the six layers call the kernel once a step, 18
    ``%decode_attention.<n>`` instructions lasting 7,067,935 ps in all;
    18 more operations name the kernel only as their operand."""
    import gzip
    import json
    from jax.profiler import ProfileData
    data = os.path.join(BENCH, "tests", "data", "v5e-decode-steps")
    with gzip.open(data + ".pbtxt.gz", "rt") as f:
        planes = list(ProfileData.from_text_proto(f.read()).planes)
    with open(data + ".json") as f:
        host = json.load(f)
    r = tr.reduce_planes(planes, phases=host["phases"],
                         step_host_starts=host["step_host_starts"])
    assert r.devices == 1
    assert r.kernel_count["decode_attention"] == 6 * 3
    assert r.kernel_s["decode_attention"] == pytest.approx(7.067935e-3)
    named = sum(1 for p in planes if p.name.startswith("/device:")
                for line in p.lines for e in line.events
                if "decode_attention" in e.name)
    assert named == 36
    assert r.window_s == pytest.approx(0.096944898)
    assert r.kernel_s["decode_attention"] < r.busy_s < r.window_s
    # the steps fill the window: every idle gap lies inside one
    assert r.idle_gaps and all(n != "between_steps" for n, _ in r.idle_gaps)
    assert r.busy_s + sum(r.idle_by_phase.values()) == \
        pytest.approx(r.window_s)
    # an excerpt of it (``--keep-trace``) is valid text proto, quotes in
    # the instructions' names included, and reduces alike
    again = ProfileData.from_text_proto(tr.excerpt(planes, 0, 1e18)).planes
    r2 = tr.reduce_planes(list(again))
    assert (r2.kernel_count, r2.busy_s, r2.window_s) == \
        (r.kernel_count, r.busy_s, r.window_s)
