"""``chip_smoke.py``'s phases on the CPU at a tiny width.

The script itself refuses to run without a TPU; these tests drive its
phase functions directly, with the device-kind profile and the compiled-
kernel check steered here (interpret mode compiles no Mosaic kernel), so
the serving, reference and DEP-vs-capacity comparisons it makes on the
chip are exercised on every run.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs.qwen2_moe_a2_7b import CONFIG  # noqa: E402
from repro.core import perf_model  # noqa: E402


def tiny(d_model: int = 256, layers: int = 2):
    """qwen2-moe's structure (shared experts, qkv bias, untied head) at a
    CPU-sized width, with the smoke's no-drop capacity."""
    moe = dataclasses.replace(CONFIG.moe, num_experts=8, top_k=2,
                              expert_ffn_dim=64, shared_ffn_dim=64,
                              num_shared_experts=2, capacity_factor=4.0)
    return dataclasses.replace(
        CONFIG, name="qwen2-moe-tiny", num_layers=layers, d_model=d_model,
        num_heads=d_model // 64, num_kv_heads=d_model // 64, head_dim=64,
        vocab_size=512, moe=moe)


def test_one_chip_phase_matches_reference(monkeypatch):
    monkeypatch.setitem(perf_model.DEVICE_KIND_PROFILES,
                        jax.devices()[0].device_kind, perf_model.TPU_V5E)
    monkeypatch.setattr(chip_smoke, "check_kernel_in_hlo", lambda text: None)
    med = chip_smoke.one_chip_phase(
        jax.devices()[0], tiny(), num_slots=4, max_context=128,
        prompts=(40, 9, 1), new=(3, 2, 3))
    assert med <= chip_smoke.REF_MEDIAN_TOL


def test_compare_rows_fails_out_of_tolerance():
    import numpy as np
    want = np.ones((3, 8))
    assert chip_smoke.compare_rows("same", want, want, 0.1) == 0.0
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.compare_rows("scrambled", -want, want, 0.1)


def test_script_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, env=env,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr


def test_four_chip_phase_on_virtual_devices():
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        sys.path.insert(0, {os.path.join(ROOT, "tests")!r})
        import jax
        import chip_smoke
        from repro.core import perf_model
        from test_chip_smoke import tiny
        perf_model.DEVICE_KIND_PROFILES[jax.devices()[0].device_kind] = \\
            perf_model.TPU_V5E
        med = chip_smoke.four_chip_phase(
            jax.devices()[:4], tiny(d_model=256, layers=2),
            max_context=128, prompt=65, new=2)
        print("ok", med)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "ok" in r.stdout
    assert "PartitionSpec('model', None, None) over 4 devices" in r.stdout
