"""repro.profiling: microbench units, profile store round-trips, telemetry
residuals, drift-triggered background refresh, and the satellite hooks
(cost-aware cache eviction, launch policy knobs, executor m_e alignment)."""
import math
import time
from types import SimpleNamespace

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import DepClusterConfig
from repro.core import FinDEPPlanner, PAPER_A6000, PlannerConfig
from repro.core.perf_model import (AlphaBeta, HardwareProfile, PROFILES,
                                   build_stage_models, fit_profile,
                                   get_profile, register_profile)
from repro.core.solver import Plan
from repro.profiling import (CalibrationResult, DriftMonitor, PlanRefresher,
                             ProfileKey, ProfileStore, StepTimer,
                             measure_attention, measure_gemm,
                             measure_all_to_all, rescale_policy_hardware)
from repro.sched import FinDEPPolicy, PlanCache

CFG = get_smoke_config("qwen2-moe-a2.7b")
CLUSTER = DepClusterConfig(num_devices=8, ag=3, eg=5)


def mk_planner(hw=PAPER_A6000, **kw):
    return FinDEPPlanner(CFG, CLUSTER, hw,
                         PlannerConfig(mem_cap_samples=8, **kw))


def synthetic_profile(name="synth"):
    """An exactly-linear 'measurement' set and its fitted profile."""
    measured = {
        "gemm": (np.linspace(1e6, 1e9, 8), 1.7e-4 + 8.6e-14
                 * np.linspace(1e6, 1e9, 8)),
        "attn": (np.linspace(1e5, 1e8, 8), 1.5e-4 + 1.5e-14
                 * np.linspace(1e5, 1e8, 8)),
        "comm": (np.linspace(2**16, 2**24, 8), 3.7e-4 + 2.5e-9
                 * np.linspace(2**16, 2**24, 8)),
    }
    profile, r2s = fit_profile(measured, name=name)
    return profile, r2s, measured


# ---------------------------------------------------------------------------
# microbench sample units
# ---------------------------------------------------------------------------

def test_gemm_samples_in_perf_model_units():
    s = measure_gemm(shapes=[(8, 16, 32), (16, 16, 32)], warmup=0, iters=1)
    assert s.kind == "gemm"
    assert s.xs == [8 * 16 * 32, 16 * 16 * 32]          # x = m*k*n
    assert all(t > 0 for t in s.ts) and len(s.ts) == 2


def test_attention_samples_in_perf_model_units():
    s = measure_attention(shapes=[(2, 16, 4, 8)], warmup=0, iters=1)
    # y = N_h * B * S^2 * (d_k + d_v)
    assert s.xs == [4 * 2 * 16 * 16 * (8 + 8)]
    assert s.ts[0] > 0


def test_comm_proxy_samples_are_bytes():
    import jax.numpy as jnp
    s = measure_all_to_all(mesh=None, sizes_bytes=[1 << 12, 1 << 14],
                           dtype=jnp.float32, warmup=0, iters=1)
    assert s.proxy                                       # no multi-dev axis
    assert s.xs == [float(1 << 12), float(1 << 14)]      # z = bytes/device
    assert all(t > 0 for t in s.ts)


def test_decode_samples_in_bytes_streamed_units():
    from repro.profiling import measure_decode_attention
    from repro.profiling.microbench import DECODE_HEAD_DIM, DECODE_KV_HEADS
    s = measure_decode_attention(shapes=[(2, 64, 0.5)], warmup=0, iters=1)
    assert s.kind == "decode"
    # z = B * c_eff * Kv * (d_k + d_v) * itemsize, c_eff = int(C * fill)
    c_eff = 32
    assert s.xs == [2.0 * c_eff * DECODE_KV_HEADS * 2 * DECODE_HEAD_DIM * 4]
    assert s.ts[0] > 0
    assert s.proxy or s.xs        # jnp stand-in off-TPU is flagged


def test_decode_fit_round_trips_and_drives_stage_models():
    """The optional decode primitive fits its own alpha-beta, survives
    the dict round-trip bit-for-bit, and replaces the prefill attention
    fit in t_a exactly when decode_context > 0."""
    from dataclasses import replace

    _, _, measured = synthetic_profile()
    zs = np.linspace(2**16, 2**24, 8)
    measured["decode"] = (zs, 2.0e-4 + 5.0e-9 * zs)
    profile, r2s = fit_profile(measured, name="decode_fit")
    assert r2s["decode"] > 0.999999
    assert profile.decode.alpha == pytest.approx(2.0e-4)
    assert profile.decode.beta == pytest.approx(5.0e-9)
    assert HardwareProfile.from_dict(profile.as_dict()) == profile

    from repro.core.perf_model import DepModelSpec
    spec = DepModelSpec.from_model_config(CFG, 256)
    no_decode_fit = replace(profile, decode=None)
    # prefill (decode_context == 0): the decode fit must not perturb t_a
    assert (build_stage_models(profile, spec, CLUSTER).t_a
            == build_stage_models(no_decode_fit, spec, CLUSTER).t_a)
    # decode phase: dedicated fit changes the attention term
    dspec = replace(spec, decode_context=512.0)
    with_fit = build_stage_models(profile, dspec, CLUSTER).t_a
    fallback = build_stage_models(no_decode_fit, dspec, CLUSTER).t_a
    assert with_fit != fallback
    # the bytes-streamed unit uses kv heads: expected beta contribution
    kv = CFG.num_kv_heads or CFG.num_heads
    expected = (256 * 512.0 * kv * 2 * CFG.head_dim
                * CLUSTER.dtype_bytes * profile.decode.beta)
    gemm_part = fallback.beta - profile.attn.beta * (
        256 * 512.0 * CFG.num_heads * 2 * CFG.head_dim)
    assert with_fit.beta == pytest.approx(gemm_part + expected)


def test_fit_consumes_microbench_samples():
    """The sample dict plugs straight into the perf-model fitting path and
    an exactly-linear sweep is recovered with R^2 ~ 1."""
    profile, r2s, measured = synthetic_profile()
    assert min(r2s.values()) > 0.999999
    assert profile.gemm.alpha == pytest.approx(1.7e-4)
    assert profile.gemm.beta == pytest.approx(8.6e-14)
    models = build_stage_models(
        profile,
        __import__("repro.core.perf_model", fromlist=["DepModelSpec"])
        .DepModelSpec.from_model_config(CFG, 256), CLUSTER)
    assert models.t_e(4.0) > 0


# ---------------------------------------------------------------------------
# profile serialization + store round-trip
# ---------------------------------------------------------------------------

def test_profile_dict_roundtrip_bit_for_bit():
    profile, _, _ = synthetic_profile()
    again = HardwareProfile.from_dict(profile.as_dict())
    assert again == profile          # float dataclass eq == bitwise here


def test_profile_registry():
    p = HardwareProfile("unit_test_prof", AlphaBeta(1e-4, 1e-12),
                        AlphaBeta(1e-4, 1e-12), AlphaBeta(1e-4, 1e-9))
    register_profile(p)
    try:
        assert get_profile("unit_test_prof") is p
        with pytest.raises(KeyError, match="unknown hardware profile"):
            get_profile("no_such_profile")
    finally:
        PROFILES.pop("unit_test_prof", None)


def test_scaled_profile_preserves_argmax():
    planner_a = mk_planner(PAPER_A6000)
    planner_b = mk_planner(PAPER_A6000.scaled(3.0))
    pa = planner_a.plan(256, 4)
    pb = planner_b.plan(256, 4)
    assert (pa.m_a, pa.r1, pa.r2, pa.order) == (pb.m_a, pb.r1, pb.r2,
                                                pb.order)
    assert pb.makespan == pytest.approx(3.0 * pa.makespan)


def test_store_roundtrip_preserves_plans_bit_for_bit(tmp_path):
    profile, r2s, measured = synthetic_profile("roundtrip")
    store = ProfileStore(tmp_path / "profiles")
    key = ProfileKey(device_kind="cpu", mesh_shape=(1,), dtype="float32")
    samples = {k: (list(map(float, xs)), list(map(float, ts)))
               for k, (xs, ts) in measured.items()}
    store.put(profile, key, name="rt", fit_r2=r2s, samples=samples)
    entry = store.get("rt")
    assert entry.profile == profile                     # bit-for-bit
    assert entry.key == key
    assert entry.samples == samples
    assert entry.fit_r2 == r2s
    # plans solved from the loaded fit ARE the plans from the fresh fit
    assert mk_planner(entry.profile).plan(256, 4) == \
        mk_planner(profile).plan(256, 4)
    # keyed lookup + staleness metadata
    assert store.get_for_key(key).name == "rt"
    assert entry.age_s < 60 and not entry.is_stale(3600)
    assert entry.is_stale(0)
    assert store.names() == ["rt"] and store.has("rt")
    with pytest.raises(KeyError):
        store.get("missing")


def test_store_ignores_unknown_schema(tmp_path):
    store = ProfileStore(tmp_path)
    profile, _, _ = synthetic_profile()
    store.put(profile, ProfileKey("cpu", (1,), "float32"), name="ok")
    (tmp_path / "bad.json").write_text('{"schema": 999, "name": "bad"}')
    (tmp_path / "junk.json").write_text("not json")
    assert store.names() == ["ok"]
    with pytest.raises(KeyError, match="schema"):
        store.get("bad")


def test_calibration_result_stores(tmp_path):
    """A (synthetic) CalibrationResult persists through put_calibration."""
    from repro.profiling.microbench import MicrobenchSamples
    profile, r2s, measured = synthetic_profile("calib")
    samples = {k: MicrobenchSamples(k, list(map(float, xs)),
                                    list(map(float, ts)),
                                    proxy=(k == "comm"))
               for k, (xs, ts) in measured.items()}
    res = CalibrationResult(profile=profile, fit_r2=r2s, samples=samples,
                            wall_s=0.1)
    assert res.comm_is_proxy and res.min_r2() > 0.99
    store = ProfileStore(tmp_path)
    entry = store.put_calibration(res, ProfileKey("cpu", (1,), "float32"))
    assert entry.comm_proxy
    assert store.load_profile(entry.name) == profile


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_residual_zero_when_fed_own_predictions():
    """Feeding the timer the model's own predictions yields exactly zero
    residual per phase and per key."""
    timer = StepTimer()
    planner = mk_planner()
    for S in (64, 256):
        plan = planner.plan(S, 4)
        for _ in range(3):
            timer.observe("decode", plan.makespan,
                          predicted_s=plan.makespan, key=("decode", S))
    assert timer.residuals() == {"decode": 0.0}
    assert timer.key_residual(("decode", 64)) == 0.0
    assert timer.phases["decode"].count == 6


def test_residual_signs_and_ewma():
    timer = StepTimer(smoothing=1.0)        # no smoothing: ewma == last
    r = timer.observe("decode", 2.0, predicted_s=1.0, key="k")
    assert r == pytest.approx(1.0)          # 100% slower than modeled
    timer.observe("decode", 0.5, predicted_s=1.0, key="k")
    assert timer.key_residual("k") == pytest.approx(-0.5)
    timer.reset_key("k")
    assert timer.key_residual("k") is None
    # phase aggregate: (2.5 - 2.0) / 2.0
    assert timer.residuals()["decode"] == pytest.approx(0.25)


def test_key_warmup_excludes_first_call_compile():
    """A key's first observation (jit compile) must not poison the EWMA:
    a one-off 100x outlier followed by on-model steps never reads as
    drift."""
    timer = StepTimer(key_warmup=1)
    timer.observe("decode", 100.0, predicted_s=1.0, key="k")  # compile
    assert timer.key_residual("k") is None
    for _ in range(3):
        timer.observe("decode", 1.0, predicted_s=1.0, key="k")
    assert timer.key_residual("k") == 0.0
    assert timer.keys["k"].count == 3


def test_measure_context_manager():
    timer = StepTimer()
    with timer.measure("prefill", predicted_s=1e-9):
        time.sleep(0.01)
    st = timer.phases["prefill"]
    assert st.count == 1 and st.measured_s >= 0.01
    assert st.last_residual > 0          # measured >> 1ns prediction


# ---------------------------------------------------------------------------
# drift-triggered refresh
# ---------------------------------------------------------------------------

class SlowRefreshPolicy:
    """First resolve is instant; every later one sleeps (a 'solver
    hiccup') and bumps r2 so refreshed plans are distinguishable."""

    name = "slowrefresh"

    def __init__(self, delay=0.5):
        self.delay = delay
        self.calls = 0

    def resolve(self, phase, seq_bucket=None, batch_per_device=None, *,
                occupancy=None, skew=None):
        self.calls += 1
        if self.calls > 1:
            time.sleep(self.delay)
        return Plan(m_a=1, r1=1, m_e=1.0, r2=self.calls, order="AASS",
                    throughput=1.0, makespan=1.0)


def test_synthetic_drift_one_resolve_never_blocks():
    """Acceptance: injected drift triggers EXACTLY one background re-solve
    for the key; lookups keep being served (by the stale plan) while the
    slow re-solve runs; nothing on the observe path ever waits on it."""
    pol = SlowRefreshPolicy(delay=0.5)
    cache = PlanCache(pol)
    monitor = DriftMonitor(cache, threshold=0.3, min_samples=2,
                           recalibrate=False)
    try:
        stale = cache.get("decode", 256, 4)
        assert stale.r2 == 1
        key = ("decode", 256, 4)
        t0 = time.perf_counter()
        warm = monitor.observe(key, measured_s=9.0, predicted_s=1.0)
        first = monitor.observe(key, measured_s=2.0, predicted_s=1.0)
        triggered = monitor.observe(key, measured_s=2.0, predicted_s=1.0)
        observe_walltime = time.perf_counter() - t0
        assert not warm                       # first call: jit-compile
        # warmup, excluded from the EWMA (9.0 would otherwise dominate)
        assert not first                      # below min_samples
        assert triggered                      # breach -> scheduled
        assert observe_walltime < 0.25        # never waits on the solve
        # stale plan keeps serving mid-refresh
        assert cache.get("decode", 256, 4) is stale
        # further drift on the same key while in flight: deduplicated
        assert not monitor.observe(key, measured_s=2.0, predicted_s=1.0)
        monitor.refresher.drain()
        assert pol.calls == 2                 # exactly one re-solve
        assert cache.get("decode", 256, 4).r2 == 2
        assert cache.stats.refreshes == 1
        assert monitor.stats.drift_events == 1
        # episode closed: residual history restarted
        assert monitor.timer.key_residual(key) is None
    finally:
        monitor.close()


def test_no_drift_no_refresh():
    pol = SlowRefreshPolicy()
    cache = PlanCache(pol)
    monitor = DriftMonitor(cache, threshold=0.5, min_samples=1,
                           recalibrate=False)
    try:
        cache.get("decode", 256, 4)
        for _ in range(5):
            assert not monitor.observe(("decode", 256, 4), 1.04, 1.0)
        monitor.refresher.drain()
        assert pol.calls == 1 and cache.stats.refreshes == 0
    finally:
        monitor.close()


def test_drift_recalibration_rescales_planner():
    planner = mk_planner()
    policy = FinDEPPolicy(planner)
    beta0 = planner.hardware.gemm.beta
    assert rescale_policy_hardware(policy, 2.0)
    assert planner.hardware.gemm.beta == pytest.approx(2.0 * beta0)
    assert planner._cache == {}              # memo dropped with the profile
    cache = PlanCache(policy)
    monitor = DriftMonitor(cache, threshold=0.5, min_samples=1,
                           recalibrate=True)
    try:
        plan = cache.get("decode", 256, 4)
        key = ("decode", 256, 4)
        monitor.observe(key, measured_s=3.0 * plan.makespan,
                        predicted_s=plan.makespan)       # key warmup
        assert monitor.observe(key, measured_s=3.0 * plan.makespan,
                               predicted_s=plan.makespan)
        monitor.refresher.drain()
        refreshed = cache.get("decode", 256, 4)
        # same schedule (uniform rescale preserves argmax), honest makespan
        assert (refreshed.m_a, refreshed.r2) == (plan.m_a, plan.r2)
        assert refreshed.makespan == pytest.approx(3.0 * plan.makespan)
    finally:
        monitor.close()


def test_recalibration_refreshes_every_entry():
    """One hardware-wide drift episode corrects everything once: the
    rescale refreshes ALL cached entries and restarts every key's
    residual history, instead of letting each stale key re-breach and
    compound the correction."""
    planner = mk_planner()
    cache = PlanCache(FinDEPPolicy(planner))
    monitor = DriftMonitor(cache, threshold=0.5, min_samples=1,
                           recalibrate=True)
    try:
        pa = cache.get("decode", 256, 4)
        cache.get("decode", 512, 4)
        key = ("decode", 256, 4)
        monitor.observe(key, 3.0 * pa.makespan, pa.makespan)  # warmup
        assert monitor.observe(key, 3.0 * pa.makespan, pa.makespan)
        monitor.refresher.drain()
        assert cache.stats.refreshes == 2          # both entries re-solved
        assert monitor.stats.drift_events == 1     # ... in ONE episode
        for k in (key, ("decode", 512, 4)):
            assert monitor.timer.key_residual(k) is None
        # both cached makespans now predict the 3x-slower hardware
        assert cache.get("decode", 512, 4).makespan > 0
        assert cache.get("decode", 256, 4).makespan == \
            pytest.approx(3.0 * pa.makespan)
    finally:
        monitor.close()


def test_cluster_from_mesh_degenerate_shapes():
    from repro.launch import steps
    full_model = SimpleNamespace(shape={"model": 8}, size=8)
    c = steps.cluster_from_mesh(full_model)      # eg capped below n
    assert c.ag + c.eg <= c.num_devices and c.eg == 7
    with pytest.raises(ValueError, match=">= 2 devices"):
        steps.cluster_from_mesh(SimpleNamespace(shape={"model": 1},
                                                size=1))


def test_refresher_in_flight_dedup_and_errors():
    class Boom:
        name = "boom"

        def resolve(self, *a, **k):
            raise RuntimeError("solver exploded")

    cache = PlanCache(Boom())
    r = PlanRefresher(cache)
    assert r.request(("decode", 1, 1))
    r.drain()
    assert r.failed == 1 and r.completed == 0      # error contained
    r.close()


def test_cache_refresh_forces_planner_resolve():
    """PlanCache.refresh must re-run Algorithm 1, not hit the planner
    memo (the policy's invalidate() hook)."""
    planner = mk_planner()
    cache = PlanCache(FinDEPPolicy(planner))
    cache.get("prefill", 256, 4)
    n = planner.solve_count
    cache.refresh(("prefill", 256, 4))
    assert planner.solve_count == n + 1
    assert cache.stats.refreshes == 1


def test_engine_drift_refresh_end_to_end():
    """Acceptance: a served workload whose measured step times dwarf the
    modeled makespans (the profile under-predicts by orders of magnitude)
    trips drift; re-solves happen in the background and every request
    still finishes."""
    import jax.numpy as jnp
    from repro.runtime import Request, ServingEngine
    hw = PAPER_A6000.scaled(1e-5, name="way_too_fast")
    eng = ServingEngine(CFG, num_slots=2, max_context=128,
                        plan_policy=FinDEPPolicy(mk_planner(hw)),
                        drift_threshold=0.5, drift_min_samples=2,
                        dtype=jnp.float32)
    try:
        rng = np.random.RandomState(0)
        reqs = [Request(prompt=list(rng.randint(0, CFG.vocab_size,
                                                size=rng.randint(4, 30))),
                        max_new_tokens=5) for _ in range(4)]
        for r in reqs:
            eng.submit(r)
        finished = eng.run()
        assert len(finished) == 4
        eng.drift.refresher.drain()
        assert eng.drift.stats.drift_events >= 1
        assert eng.plan_cache.stats.refreshes >= 1
        assert eng.drift.refresher.failed == 0
        res = eng.telemetry.residuals()
        assert res.get("decode") is not None
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# satellite: cost-aware bounded PlanCache
# ---------------------------------------------------------------------------

class TunableLatencyPolicy:
    name = "tunable"

    def __init__(self):
        self.delay = 0.0
        self.calls = 0

    def resolve(self, phase, seq_bucket=None, batch_per_device=None, *,
                occupancy=None, skew=None):
        self.calls += 1
        if self.delay:
            time.sleep(self.delay)
        return Plan(m_a=1, r1=1, m_e=1.0, r2=self.calls, order="AASS",
                    throughput=1.0, makespan=1.0)


def test_cache_cost_aware_eviction():
    pol = TunableLatencyPolicy()
    cache = PlanCache(pol, capacity=2)
    pol.delay = 0.05
    cache.get("prefill", 64, 1)              # expensive solve ...
    cache.get("prefill", 64, 1)              # ... and reused -> high score
    pol.delay = 0.0
    cache.get("prefill", 128, 1)             # cheap, never reused
    cache.get("prefill", 256, 1)             # third entry: over capacity
    assert len(cache) == 2
    assert cache.stats.evictions == 1
    keys = set(cache.entries())
    assert ("prefill", 64, 1) in keys        # protected by hits x latency
    assert ("prefill", 128, 1) not in keys   # the zero-score victim
    assert ("prefill", 256, 1) in keys       # fresh entry never self-evicts
    # evicted shape re-solves on next sight
    n = pol.calls
    cache.get("prefill", 128, 1)
    assert pol.calls == n + 1


def test_cache_invalidate():
    pol = TunableLatencyPolicy()
    cache = PlanCache(pol)
    cache.get("decode", 64, 1)
    assert cache.invalidate(("decode", 64, 1))
    assert not cache.invalidate(("decode", 64, 1))
    assert cache.stats.invalidations == 1
    cache.get("decode", 64, 1)
    assert pol.calls == 2


def test_cache_unbounded_by_default():
    pol = TunableLatencyPolicy()
    cache = PlanCache(pol)
    for S in range(1, 30):
        cache.get("prefill", S, 1)
    assert len(cache) == 29 and cache.stats.evictions == 0


# ---------------------------------------------------------------------------
# satellite: engine profile knobs + launch policy knobs
# ---------------------------------------------------------------------------

def test_engine_profile_kwarg_retunes_planner():
    import jax.numpy as jnp
    from repro.runtime import ServingEngine
    planner = mk_planner()
    hw = PAPER_A6000.scaled(2.0, name="a6000_x2")
    eng = ServingEngine(CFG, num_slots=1, max_context=64,
                        plan_policy=FinDEPPolicy(planner),
                        profile=hw, dtype=jnp.float32)
    assert planner.hardware is hw
    eng.close()


def test_engine_profile_by_name_from_store(tmp_path):
    import jax.numpy as jnp
    from repro.runtime import ServingEngine
    profile, _, _ = synthetic_profile("stored_for_engine")
    store = ProfileStore(tmp_path)
    store.put(profile, ProfileKey("cpu", (1,), "float32"),
              name="stored_for_engine")
    planner = mk_planner()
    eng = ServingEngine(CFG, num_slots=1, max_context=64,
                        plan_policy=FinDEPPolicy(planner),
                        profile="stored_for_engine", profile_store=store,
                        dtype=jnp.float32)
    assert planner.hardware == profile
    eng.close()


def test_launch_policy_knobs():
    from repro.launch import steps
    mesh = SimpleNamespace(shape={"data": 2, "model": 4}, size=8)
    cluster = steps.cluster_from_mesh(mesh)
    assert (cluster.num_devices, cluster.ag, cluster.eg) == (8, 2, 4)
    plan = steps.resolve_launch_plan(CFG, mesh, "findep", 256,
                                     batch_per_device=4)
    assert plan is not None and plan.r1 * plan.m_a == 4
    # decode mode resolves through the decode phase; named baselines work
    seq = steps.resolve_launch_plan(CFG, mesh, "sequential", 256,
                                    mode="decode", batch_per_device=2)
    assert seq.r2 == 1
    # policy objects pass through untouched
    pol = FinDEPPolicy(mk_planner())
    assert steps.resolve_launch_plan(CFG, mesh, pol, 256,
                                     batch_per_device=4) == \
        pol.resolve("prefill", 256, 4)
    # non-MoE config / no mesh -> no schedule
    dense = get_smoke_config("qwen2-1.5b")
    assert steps.resolve_launch_plan(dense, mesh, "findep", 256) is None
    assert steps.resolve_launch_plan(CFG, None, "findep", 256) is None


def test_launch_policy_with_calibrated_store_profile(tmp_path):
    from repro.launch import steps
    profile, _, _ = synthetic_profile("launch_fit")
    store = ProfileStore(tmp_path)
    store.put(profile, ProfileKey("cpu", (1,), "float32"),
              name="launch_fit")
    mesh = SimpleNamespace(shape={"data": 2, "model": 4}, size=8)
    pol = steps.launch_policy(CFG, mesh, "findep", profile="launch_fit",
                              profile_store=store)
    assert pol.planner.hardware == profile


# ---------------------------------------------------------------------------
# satellite: executor honors the solved m_e granularity
# ---------------------------------------------------------------------------

def test_exec_program_carries_floored_me():
    plan = Plan(m_a=4, r1=2, m_e=3.7, r2=2, order="ASAS",
                throughput=1.0, makespan=1.0)
    prog = plan.exec_program()
    assert (prog.graph.r2, prog.graph.order, prog.graph.m_e) == \
        (2, "ASAS", 3)
    assert prog.graph.r1 == 2          # defaults to the plan's stream split
    assert prog.interleave == "streams"
    tiny = Plan(m_a=1, r1=1, m_e=0.4, r2=1, order="AASS",
                throughput=1.0, makespan=1.0)
    assert tiny.exec_program().graph.m_e == 1
    assert plan.exec_program(streams=4).graph.r1 == 4


def test_expert_capacity_honors_plan_granularity():
    """The executor's capacity request (multiple_of = r2 * m_e) yields
    chunk sizes that are multiples of the solver's modeled m_e and never
    shrinks capacity (no new drops)."""
    from repro.models import moe as moe_lib
    mcfg = CFG.moe
    r2, m_e = 4, 3
    base = moe_lib.expert_capacity(100, mcfg, multiple_of=r2)
    aligned = moe_lib.expert_capacity(100, mcfg, multiple_of=r2 * m_e)
    assert aligned >= base
    assert aligned % (r2 * m_e) == 0
    assert (aligned // r2) % m_e == 0        # per-chunk tokens align to m_e


# ---------------------------------------------------------------------------
# satellite: per-primitive drift attribution (task-tagged residuals)
# ---------------------------------------------------------------------------

def test_exec_schedule_shim_is_gone():
    """PR 5's one-release ``ExecSchedule``/``Plan.exec_schedule()`` shims
    are removed: the executor consumes ``ExecProgram``/``TaskGraph``."""
    import repro.core.solver as solver_mod
    assert not hasattr(solver_mod, "ExecSchedule")
    plan = Plan(m_a=1, r1=1, m_e=1.0, r2=2, order="ASAS",
                throughput=1.0, makespan=1.0)
    assert not hasattr(plan, "exec_schedule")


def test_fit_primitive_scales_recovers_known_scales():
    from repro.profiling import fit_primitive_scales
    true = {"gemm": 1.0, "attn": 1.3, "comm": 2.0}
    rows = []
    comps = [(1.0, 0.5, 0.2), (0.3, 0.2, 1.5), (0.5, 1.0, 0.1),
             (0.9, 0.1, 0.9)]
    for g, a, c in comps:
        rows.append(({"gemm": g, "attn": a, "comm": c},
                     true["gemm"] * g + true["attn"] * a + true["comm"] * c))
    scales = fit_primitive_scales(rows)
    assert scales is not None
    for k, v in true.items():
        assert scales[k] == pytest.approx(v, rel=1e-9), k


def test_fit_primitive_scales_unidentifiable_falls_back():
    from repro.profiling import fit_primitive_scales
    # one composition repeated: rank 1 < 3 active primitives -> None
    row = ({"gemm": 1.0, "attn": 0.5, "comm": 0.2}, 2.0)
    assert fit_primitive_scales([row, row, row]) is None
    # too few rows / no rows
    assert fit_primitive_scales([row]) is None
    assert fit_primitive_scales([]) is None
    # zero-signal primitive keeps scale 1.0 while the rest are fitted
    rows = [({"gemm": 1.0, "attn": 0.0, "comm": 0.5}, 1.0 + 2.0 * 0.5),
            ({"gemm": 0.2, "attn": 0.0, "comm": 1.5}, 0.2 + 2.0 * 1.5)]
    scales = fit_primitive_scales(rows)
    assert scales["attn"] == 1.0
    assert scales["comm"] == pytest.approx(2.0, rel=1e-9)


def test_steptimer_accumulates_breakdown_past_warmup():
    timer = StepTimer(key_warmup=1)
    bd = {"gemm": 0.6, "attn": 0.3, "comm": 0.1}
    timer.observe("decode", 2.0, predicted_s=1.0, key="k", breakdown=bd)
    st = timer.keys["k"]
    assert st.breakdown == {} and st.measured_s == 0.0   # warmup excluded
    timer.observe("decode", 2.0, predicted_s=1.0, key="k", breakdown=bd)
    timer.observe("decode", 2.0, predicted_s=1.0, key="k", breakdown=bd)
    assert st.measured_s == pytest.approx(4.0)
    assert st.predicted_s == pytest.approx(2.0)
    assert st.breakdown["gemm"] == pytest.approx(1.2)
    timer.reset_key("k")
    assert timer.keys["k"].breakdown == {}


def test_drift_per_primitive_rescales_comm_separately():
    """Task-tagged residuals: keys with different gemm/attn/comm
    compositions identify a comm-only slowdown, so the recalibrating
    episode retunes alpha_c/beta_c by ~2x while the compute terms stay
    put (the uniform rescale would have inflated everything)."""
    planner = mk_planner()
    policy = FinDEPPolicy(planner)
    cache = PlanCache(policy)
    monitor = DriftMonitor(cache, threshold=0.2, min_samples=2,
                           recalibrate=True, per_primitive=True)
    hw0 = planner.hardware
    comps = {("decode", 256, 1): (0.8, 0.15, 0.05),
             ("decode", 256, 2): (0.1, 0.1, 0.8),
             ("decode", 256, 4): (0.3, 0.6, 0.1)}
    try:
        for (_, s, b), _comp in comps.items():
            cache.get("decode", s, b)
        # comm runs 2x slower than modeled; compute on time. Observations
        # interleave across keys (as a serving loop's steps do), so by
        # the time one key breaches, all three compositions carry tags.
        for _ in range(4):       # warmup + min_samples + breach
            for key, (g, a, c) in comps.items():
                bd = {"gemm": g, "attn": a, "comm": c}
                monitor.observe(key, g + a + 2.0 * c, 1.0, breakdown=bd)
        monitor.refresher.drain()
        assert monitor.stats.drift_events >= 1
        scales = monitor.stats.last_scales
        assert scales is not None
        assert scales["comm"] == pytest.approx(2.0, rel=1e-6)
        assert scales["gemm"] == pytest.approx(1.0, rel=1e-6)
        assert planner.hardware.comm.beta == \
            pytest.approx(2.0 * hw0.comm.beta, rel=1e-6)
        assert planner.hardware.gemm.beta == \
            pytest.approx(hw0.gemm.beta, rel=1e-6)
    finally:
        monitor.close()


def test_drift_without_tags_falls_back_to_uniform():
    planner = mk_planner()
    cache = PlanCache(FinDEPPolicy(planner))
    monitor = DriftMonitor(cache, threshold=0.5, min_samples=1,
                           recalibrate=True, per_primitive=True)
    hw0 = planner.hardware
    try:
        plan = cache.get("decode", 256, 4)
        key = ("decode", 256, 4)
        monitor.observe(key, 3.0 * plan.makespan, plan.makespan)  # warmup
        assert monitor.observe(key, 3.0 * plan.makespan, plan.makespan)
        monitor.refresher.drain()
        assert monitor.stats.last_scales is None       # uniform fallback
        assert planner.hardware.gemm.beta == \
            pytest.approx(3.0 * hw0.gemm.beta)
        assert planner.hardware.comm.beta == \
            pytest.approx(3.0 * hw0.comm.beta)
    finally:
        monitor.close()


def test_plan_breakdown_flows_through_engine_observe():
    """Solved plans carry the lowered graph's gemm/attn/comm split and
    the engine's observe path forwards it into the timer's key sums."""
    planner = mk_planner()
    plan = planner.plan(256, 4)
    assert plan.breakdown is not None
    assert plan.breakdown.total == pytest.approx(plan.makespan, rel=1e-9)
    timer = StepTimer(key_warmup=0)
    timer.observe("decode", plan.makespan, predicted_s=plan.makespan,
                  key="k", breakdown=plan.breakdown.as_dict())
    st = timer.keys["k"]
    assert sum(st.breakdown.values()) == pytest.approx(plan.makespan)


# ---------------------------------------------------------------------------
# satellite: periodic background re-calibration (stale stored profile)
# ---------------------------------------------------------------------------

def _stub_calibration(name="recal"):
    profile, r2s, _ = synthetic_profile(name)
    samples = {k: SimpleNamespace(as_xt=lambda: ([1.0, 2.0], [1e-3, 2e-3]),
                                  proxy=(k == "comm"))
               for k in ("gemm", "attn", "comm")}
    return CalibrationResult(profile=profile, fit_r2=r2s, samples=samples,
                             wall_s=0.01)


def test_periodic_recalibrator_runs_when_stale(tmp_path):
    from repro.profiling import PeriodicRecalibrator
    planner = mk_planner()
    cache = PlanCache(FinDEPPolicy(planner))
    cache.get("prefill", 256, 4)
    store = ProfileStore(tmp_path)
    result = _stub_calibration()
    recal = PeriodicRecalibrator(
        cache, store, key=ProfileKey("cpu", (1,), "float32"),
        max_age_s=3600.0, calibrate_fn=lambda: result,
        poll_interval_s=0.0)
    try:
        assert recal.due()                      # empty store = stale
        assert recal.maybe_recalibrate()
        recal.drain()
        assert recal.recalibrations == 1
        assert store.get_for_key(recal.key).profile == result.profile
        assert planner.hardware == result.profile     # policy reprofiled
        assert cache.stats.refreshes == 1             # entry re-solved
        # fresh profile: not due, no second run
        assert not recal.due()
        assert not recal.maybe_recalibrate()
        # force path still dedups through the worker and reruns
        assert recal.maybe_recalibrate(force=True)
        recal.drain()
        assert recal.recalibrations == 2
    finally:
        recal.close()


def test_engine_wires_periodic_recalibration(tmp_path):
    """ServingEngine(recalibrate_max_age_s=...) polls the store each step
    without ever blocking on a microbenchmark."""
    from repro.runtime.engine import ServingEngine
    store = ProfileStore(tmp_path)
    profile, _, _ = synthetic_profile("fresh")
    store.put(profile, ProfileKey.for_host(None), name="fresh")
    eng = ServingEngine(CFG, num_slots=1, max_context=64,
                        plan_policy=FinDEPPolicy(mk_planner()),
                        profile_store=store, recalibrate_max_age_s=3600.0)
    try:
        assert eng.recalibrator is not None
        assert not eng.step()                  # idle; poll must be a no-op
        assert eng.recalibrator.recalibrations == 0
        # stale store -> a forced pass recalibrates in the background
        eng.recalibrator.calibrate_fn = lambda: _stub_calibration("eng")
        assert eng.recalibrator.maybe_recalibrate(force=True)
        eng.recalibrator.drain()
        assert eng.recalibrator.recalibrations == 1
        assert eng.plan_policy.planner.hardware == \
            _stub_calibration("eng").profile
    finally:
        eng.close()


def test_recalibrating_episode_never_compounds_while_refresh_in_flight():
    """A second breach arriving before the first episode's re-solves land
    must NOT rescale again: the stale entries still serve old predictions,
    so re-rescaling would compound the correction (2x -> 4x -> ...)."""
    pol = SlowRefreshPolicy(delay=0.5)
    cache = PlanCache(pol)
    planner_like = SimpleNamespace(
        hardware=PAPER_A6000, set_hardware=lambda hw: scales_applied.append(hw))
    pol.planner = planner_like
    scales_applied = []
    monitor = DriftMonitor(cache, threshold=0.3, min_samples=1,
                           recalibrate=True, per_primitive=False)
    try:
        cache.get("decode", 256, 4)
        key = ("decode", 256, 4)
        monitor.observe(key, 2.0, 1.0)                      # warmup
        assert monitor.observe(key, 2.0, 1.0)               # episode 1
        # refresh in flight (slow solver): further breaches on OTHER
        # observations must not start a second rescaling episode
        for _ in range(3):
            assert not monitor.observe(key, 2.0, 1.0)
        assert len(scales_applied) == 1                     # ONE rescale
        monitor.refresher.drain()
        assert monitor.stats.drift_events == 1
    finally:
        monitor.close()
