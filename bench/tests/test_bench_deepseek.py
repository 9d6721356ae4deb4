"""The deepseek_v2 family in the benchmark: its work counters by hand, the
file's mapping onto the program, and the harness end to end on the CPU at
test widths (Pallas kernels interpreted)."""
import dataclasses
import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data")
sys.path.insert(0, BENCH)

import harness  # noqa: E402

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2 ** 33 + 5


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(name,
                                                  os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ds = _load("models/deepseek_v2.py", "test_bench_ds_model")


def _cfg():
    with open(os.path.join(BENCH, "configs",
                           "deepseek-v2-lite.7l.json")) as f:
        return json.load(f)


def test_weight_bytes_at_seven_layers():
    # embedding and head 2 x 102400 x 2048; per layer attention
    # 2048x3072 + 2048x576 + 512 + 512x4096 + 2048x2048 and two norms;
    # the dense layer 3 x 2048 x 10944; each MoE layer a router 2048x64,
    # 64 experts of 3 x 2048 x 1408 and shared 3 x 2048 x 2816
    assert ds.weight_bytes(_cfg(), 2) == 8_019_053_568


def test_token_flops_by_hand():
    cfg = _cfg()
    attn = (2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256
            + 16 * 128 * 2048)
    dense = 3 * 2048 * 10944
    moe = 2048 * 64 + 6 * 3 * 2048 * 1408 + 3 * 2048 * 2816
    body = 7 * attn + dense + 6 * moe
    assert ds.token_flops(cfg, head=False) == 2 * body
    assert ds.token_flops(cfg) == 2 * (body + 2048 * 102400)
    # one query over 3000 keys, expanded: 192-wide scores and 128-wide
    # values of 16 heads, 7 layers
    assert ds.attention_flops(cfg, 3000) == 2 * 7 * 16 * (192 + 128) * 3000


def test_latent_decode_attention_work_by_hand():
    flops, nbytes = ds.decode_attention_work(_cfg(), [200, 1800], 2)
    # 576-wide scores and 512-wide values for 16 heads per key
    assert flops == 2 * 16 * (576 + 512) * 2000
    # each key's 576 latent values once; per row 16 queries of 576 and
    # 16 outputs of 512
    assert nbytes == 2 * (576 * 2000 + 2 * 16 * (576 + 512))


def test_file_maps_to_the_published_block():
    """The file's keys give the registry's DeepSeek-V2-Lite at 7 layers,
    with the file's serving capacity."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config
    system = _load("systems/deepseek_v2.py", "test_bench_ds_system")
    cfg = _cfg()
    got = system.model_config(cfg)
    want = get_config("deepseek-v2-lite")
    want = dataclasses.replace(
        want, name=cfg["name"], num_layers=7,
        source=cfg["source"],
        moe=dataclasses.replace(want.moe, capacity_factor=10.67))
    assert got == want
    assert got.moe.capacity_factor >= got.moe.num_experts / got.moe.top_k


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    stand_in = {"dsv2lite.longchat": "tiny.dsv2"}

    def cell(m):
        m = dict(m)
        if "workloads" in m:
            m["workloads"] = [stand_in[w] for w in m["workloads"]
                              if w in stand_in]
        return m
    return {"workloads": [
        {"name": "tiny.dsv2", "config": "tiny-dsv2", "traffic": "tiny-chat",
         "chips": 1}],
        "configs": [{"name": "tiny-dsv2",
                     "file": "bench/tests/data/tiny-dsv2.json"}],
        "end_to_end": [cell(m) for m in real["end_to_end"]],
        "per_layer": [cell(m) for m in real["per_layer"]]}


def test_run_is_correct_at_test_widths():
    args = harness.parse(["--workload", "tiny.dsv2", "--seed", str(SEED),
                          "--seconds", "2", "--trace", "0"])
    res = harness.run_cell(args, spec=_spec(), t_start=0.0,
                           require_tpu=False, peaks=PEAKS, cache=False,
                           mix_dir=DATA, log=lambda m: None)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"tpot_mean_ms", "setup_s"} <= set(res["metrics"])


def test_precision_control_is_not_correct():
    """At test widths the program passes the test file's mean-gap limit
    on the requests whose float8 control fails it."""
    import control
    ses = harness.Session(_spec(), "tiny.dsv2", require_tpu=False,
                          peaks=PEAKS, cache=False, mix_dir=DATA,
                          log=lambda m: None)
    limits = ses.cfg["compare"]["limits"]
    r = control.readings(ses, SEED, 2.0)
    assert r["tokens"] >= ses.cfg["compare"]["min_tokens"] // 2
    assert ses.corr.judge(r["program"], limits)[0] is True
    assert ses.corr.judge(r["control"], limits)[0] is False
