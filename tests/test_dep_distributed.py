"""DEP shard_map execution vs the dense oracle — runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=4 (the main pytest process
stays single-device)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


@pytest.mark.slow
def test_dep_seq_mode_matches_dense_oracle():
    out = run_sub(textwrap.dedent("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.models import moe as moe_lib
        from repro.models.transformer import ExecutionContext
        from repro.core import dep
        from repro.core.solver import Plan
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,2), ("data","model"))
        cfg = get_smoke_config("qwen2-moe-a2.7b")
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=8.0))
        key = jax.random.PRNGKey(1)
        params = moe_lib.moe_init(key, cfg.d_model, cfg.moe, 4)
        x = jax.random.normal(key, (4, 8, cfg.d_model), jnp.float32)
        y_ref, _ = moe_lib.moe_apply_dense(params, x, cfg.moe, 4)
        ctx = ExecutionContext(mesh=mesh, moe_impl="dep")
        # the (2, "AASS", 3) case exercises the m_e-aligned capacity:
        # chunk sizes are multiples of the solver's modeled granularity
        for r2, order, m_e in [(1,"AASS",1),(2,"ASAS",1),(4,"AASS",1),
                               (2,"AASS",3)]:
            plan = Plan(m_a=1,r1=1,m_e=m_e,r2=r2,order=order,
                        throughput=0,makespan=0)
            with mesh:
                y, _ = jax.jit(lambda p, x: dep.moe_apply_dep(
                    p, x, cfg.moe, ctx, 4, plan=plan.exec_graph()))(
                    params, x)
            err = float(jnp.max(jnp.abs(y - y_ref)))
            assert err < 1e-5, (r2, order, err)
            print("ok", r2, order, err)
    """))
    assert out.count("ok") == 4


@pytest.mark.slow
def test_dep_decode_mode_and_grads():
    out = run_sub(textwrap.dedent("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.models import moe as moe_lib
        from repro.models.transformer import ExecutionContext
        from repro.core import dep
        from repro.core.solver import Plan
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,2), ("data","model"))
        cfg = get_smoke_config("qwen2-moe-a2.7b")
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=8.0))
        key = jax.random.PRNGKey(1)
        params = moe_lib.moe_init(key, cfg.d_model, cfg.moe, 4)
        # decode mode (S=1 < mesh model size -> replicated-token psum path)
        xd = jax.random.normal(key, (4, 1, cfg.d_model), jnp.float32)
        y_ref, _ = moe_lib.moe_apply_dense(params, xd, cfg.moe, 4)
        ctx = ExecutionContext(mesh=mesh, moe_impl="dep")
        with mesh:
            y, _ = jax.jit(lambda p, x: dep.moe_apply_dep(
                p, x, cfg.moe, ctx, 4))(params, xd)
        assert float(jnp.max(jnp.abs(y - y_ref))) < 1e-5
        print("ok decode")
        # the replicated-token path honors the solved order: ASAS (shared
        # expert split across chunk boundaries) must match the oracle too
        for order in ("ASAS", "AASS"):
            plan = Plan(m_a=1, r1=1, m_e=1, r2=2, order=order,
                        throughput=0, makespan=0)
            with mesh:
                y, _ = jax.jit(lambda p, x: dep.moe_apply_dep(
                    p, x, cfg.moe, ctx, 4,
                    plan=plan.exec_graph()))(params, xd)
            assert float(jnp.max(jnp.abs(y - y_ref))) < 1e-5, order
            print("ok decode", order)
        # gradients flow through the all_to_all path
        x = jax.random.normal(key, (4, 8, cfg.d_model), jnp.float32)
        def loss(p):
            with mesh:
                y, aux = dep.moe_apply_dep(p, x, cfg.moe, ctx, 4)
            return jnp.sum(y**2) + aux
        g = jax.jit(jax.grad(loss))(params)
        finite = all(bool(jnp.all(jnp.isfinite(l)))
                     for l in jax.tree.leaves(g))
        nonzero = any(float(jnp.max(jnp.abs(l))) > 0
                      for l in jax.tree.leaves(g))
        assert finite and nonzero
        print("ok grads")
    """))
    assert "ok decode" in out and "ok grads" in out


@pytest.mark.slow
def test_seqsharded_decode_attention_matches_local():
    out = run_sub(textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.models.attention import _decode_core_seqsharded
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,2), ("data","model"))
        key = jax.random.PRNGKey(0)
        B, C, Kv, H, D = 4, 64, 2, 8, 32
        ks = jax.random.split(key, 5)
        q = jax.random.normal(ks[0], (B, 1, H, D), jnp.float32)
        kn = jax.random.normal(ks[1], (B, Kv, 1, D), jnp.float32)
        vn = jax.random.normal(ks[2], (B, Kv, 1, D), jnp.float32)
        ck = jax.random.normal(ks[3], (B, Kv, C, D), jnp.float32)
        cv = jax.random.normal(ks[4], (B, Kv, C, D), jnp.float32)
        index = jnp.asarray(37, jnp.int32)
        with mesh:
            out, nk, nv = jax.jit(lambda *a: _decode_core_seqsharded(
                *a, mesh, "model", ("data",), False))(
                q, kn, vn, ck, cv, index)
        # local reference
        import math
        ck2 = ck.at[:, :, 37].set(kn[:, :, 0])
        cv2 = cv.at[:, :, 37].set(vn[:, :, 0])
        valid = jnp.arange(C) <= 37
        g = H // Kv
        qh = q[:, 0].reshape(B, Kv, g, D)
        lg = jnp.einsum("bkgd,bksd->bkgs", qh, ck2) / math.sqrt(D)
        lg = jnp.where(valid[None,None,None], lg, -1e30)
        p = jax.nn.softmax(lg, -1)
        ref = jnp.einsum("bkgs,bksd->bkgd", p, cv2).reshape(B,1,H,D)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 1e-5, err
        assert float(jnp.max(jnp.abs(nk - ck2))) < 1e-6
        print("ok", err)
    """))
    assert "ok" in out


@pytest.mark.slow
def test_interleaved_streams_bit_identical_to_off():
    """The tentpole bit-parity lock: for ONE lowered graph, the
    ``interleave="streams"`` emission (scheduled start order, default
    priority hints) produces bit-identical outputs to the
    ``interleave="off"`` walk — sequence AND replicated-decode dispatch,
    ASAS and AASS, r1 in {1, 2, 4} — and both match the dense oracle.
    Streams slice capacity, not routing, so the reorder commutes."""
    out = run_sub(textwrap.dedent("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.models import moe as moe_lib
        from repro.models.transformer import ExecutionContext
        from repro.core import dep
        from repro.core.taskgraph import ExecProgram, lower_exec
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        cfg = get_smoke_config("qwen2-moe-a2.7b")
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=8.0))
        key = jax.random.PRNGKey(1)
        params = moe_lib.moe_init(key, cfg.d_model, cfg.moe, 4)
        ctx = ExecutionContext(mesh=mesh, moe_impl="dep")
        xs = jax.random.normal(key, (4, 8, cfg.d_model), jnp.float32)
        xd = jax.random.normal(key, (4, 1, cfg.d_model), jnp.float32)
        cases = [(xs, "seq"), (xd, "dec")]
        for x, tag in cases:
            y_ref, _ = moe_lib.moe_apply_dense(params, x, cfg.moe, 4)
            for order in ("ASAS", "AASS"):
                for r1 in (1, 2, 4):
                    g = lower_exec(2, order, 1, r1=r1)
                    def run(prog):
                        with mesh:
                            y, _ = jax.jit(
                                lambda p, xx: dep.moe_apply_dep(
                                    p, xx, cfg.moe, ctx, 4,
                                    plan=prog))(params, x)
                        return y
                    y_off = run(ExecProgram(g, interleave="off"))
                    y_str = run(ExecProgram(g, interleave="streams"))
                    assert jnp.array_equal(y_off, y_str), \\
                        (tag, order, r1)
                    err = float(jnp.max(jnp.abs(y_str - y_ref)))
                    assert err < 1e-5, (tag, order, r1, err)
                    print("ok", tag, order, r1)
    """))
    assert out.count("ok") == 12
