"""``decode_attention_ms`` on the recorded chip trace of three qwen2moe.chat
decode steps (``tests/data/v5e-decode-steps``): 18 kernel calls lasting
7,067,935 ps in all, six layers a step."""
import gzip
import json
import os
import sys
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import trace_reduce as tr  # noqa: E402

reader = harness.load_module(
    os.path.join(BENCH, "metrics", "decode_attention_ms.py"),
    "bench_metric_decode_attention_ms")


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    data = os.path.join(BENCH, "tests", "data", "v5e-decode-steps")
    with gzip.open(data + ".pbtxt.gz", "rt") as f:
        planes = list(ProfileData.from_text_proto(f.read()).planes)
    with open(data + ".json") as f:
        host = json.load(f)
    return tr.reduce_planes(planes, phases=host["phases"],
                            step_host_starts=host["step_host_starts"])


def _run(trace, layers, steps):
    return SimpleNamespace(
        trace=trace, cfg={"num_hidden_layers": layers},
        traced_steps=[SimpleNamespace(keys=[300, 40]) for _ in range(steps)])


def test_kernel_ms_per_step(reduced):
    assert reader.read(_run(reduced, 6, 3)) == pytest.approx(
        7.067935 / 3)


@pytest.mark.parametrize("layers, steps", [(7, 3), (6, 4)])
def test_nothing_read_unless_one_call_per_layer_a_step(reduced, layers,
                                                       steps):
    assert reader.read(_run(reduced, layers, steps)) is None


def test_nothing_read_without_a_trace():
    assert reader.read(_run(None, 6, 3)) is None
