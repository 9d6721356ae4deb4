"""The harness end to end on the CPU, at a configuration of test widths with
the Pallas kernels interpreted: a run prints a well-formed result, the real
entry refuses a machine without a TPU, and a broken serving path turns
``correct`` false."""
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data")
sys.path.insert(0, BENCH)

import harness  # noqa: E402

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2 ** 31 + 77


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    # the tiny cell stands for the real one, metric for metric
    stand_in = {"qwen2moe.chat": "tiny.chat"}
    metrics = []
    for m in real["end_to_end"] + real["per_layer"]:
        m = dict(m)
        if "workloads" in m:
            m["workloads"] = [stand_in[w] for w in m["workloads"]]
        metrics.append(m)
    return {"workloads": [
        {"name": "tiny.chat", "config": "tiny-moe", "traffic": "tiny-chat",
         "chips": 1}],
        "configs": [{"name": "tiny-moe",
                     "file": "bench/tests/data/tiny-moe.json"}],
        "end_to_end": metrics[:len(real["end_to_end"])],
        "per_layer": metrics[len(real["end_to_end"]):]}


def _run(cell, seconds=2.0, trace=0):
    args = harness.parse(["--workload", cell, "--seed", str(SEED),
                          "--seconds", str(seconds), "--trace", str(trace)])
    return harness.run_cell(args, spec=_spec(), t_start=0.0,
                            require_tpu=False, peaks=PEAKS, cache=False,
                            mix_dir=DATA, log=lambda m: None)


@pytest.mark.parametrize("trace", [pytest.param(0, id="tiny.chat"),
                                   pytest.param(1, id="tiny.chat-trace")])
def test_run_prints_a_well_formed_result(trace):
    res = _run("tiny.chat", trace=trace)
    line = json.loads(json.dumps(res))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    spec = _spec()
    want = {m["name"] for m in
            (spec["per_layer"] if trace else spec["end_to_end"])}
    # the CPU trace holds no decode_attention kernel and no device plane
    assert set(line["metrics"]) <= want
    if not trace:
        assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert line["device"]["count"] == 1
    for name, r in line["compared"].items():
        assert r["value"] <= r["limit"], name


def test_window_timings_stop_at_the_close():
    """The system's own timings are taken when the window closes, before
    the drain serves on: no decode step that ended later is in them."""
    ses = harness.Session(_spec(), "tiny.chat", require_tpu=False,
                          peaks=PEAKS, cache=False, mix_dir=DATA,
                          log=lambda m: None)
    ses.build(SEED)
    ses.sut.reset_stats()
    t0, t1, timings = ses.window(ses.requests(SEED, 1.0), 1.0)
    ended = [s for s in ses.client.steps if s.end < t1]
    assert 0 < timings["decode_n"] <= len(ended)
    assert timings["decode_s"] <= sum(s.end - s.start for s in ended)
    ses.free()


def test_entry_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "qwen2moe.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_altered_token_is_not_correct(monkeypatch):
    """A token altered where it is produced: every sampled id moved by
    one."""
    import repro.runtime.engine as eng
    real = eng.sample

    def altered(key, logits, temperature, top_k=0):
        return (real(key, logits, temperature, top_k) + 1) \
            % logits.shape[-1]
    monkeypatch.setattr(eng, "sample", altered)
    assert _run("tiny.chat")["correct"] is False


def test_unchanged_state_is_not_correct(monkeypatch):
    """A decode step that returns its state (the KV cache) unchanged."""
    import repro.runtime.engine as eng
    real = eng.ServingEngine._decode_step

    def stale(self, params, tokens, caches, *a, **kw):
        nxt, _, mstats, logits = real(self, params, tokens, caches, *a, **kw)
        return nxt, caches, mstats, logits
    monkeypatch.setattr(eng.ServingEngine, "_decode_step", stale)
    assert _run("tiny.chat")["correct"] is False


def test_precision_control_is_not_correct():
    """The control: the reference with its weights rounded to float8, put
    in the program's place, fails the limits on the very requests whose
    served tokens pass them. At these test widths the program's mean gap
    read at most 0.016 on eleven seeds of the two test mixes, the
    control's at least 0.036 (CPU); the test configuration's limit is
    0.025."""
    import control
    ses = harness.Session(_spec(), "tiny.chat", require_tpu=False,
                          peaks=PEAKS, cache=False, mix_dir=DATA,
                          log=lambda m: None)
    limits = ses.cfg["compare"]["limits"]
    for seed in (SEED, SEED + 1):
        r = control.readings(ses, seed, 2.0)
        assert r["tokens"] >= ses.cfg["compare"]["min_tokens"] // 2
        assert ses.corr.judge(r["program"], limits)[0] is True
        assert ses.corr.judge(r["control"], limits)[0] is False
