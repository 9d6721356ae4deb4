"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs jnp oracle."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.moe_gemm.ops import moe_gemm
from repro.kernels.moe_gemm.ref import moe_gemm_ref
from repro.kernels.rg_lru.ops import rg_lru_scan
from repro.kernels.rg_lru.ref import rg_lru_scan_ref

KEY = jax.random.PRNGKey(0)


def tol(dt):
    return dict(rtol=2e-2, atol=5e-2) if dt == jnp.bfloat16 else \
        dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("E,C,M,H", [(2, 128, 128, 256), (4, 256, 256, 512),
                                     (1, 512, 512, 128), (3, 384, 128, 640)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gemm(E, C, M, H, dtype):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (E, C, M), dtype)
    wg = jax.random.normal(ks[1], (E, M, H), dtype) * 0.05
    wu = jax.random.normal(ks[2], (E, M, H), dtype) * 0.05
    wd = jax.random.normal(ks[3], (E, H, M), dtype) * 0.05
    y = moe_gemm(x, wg, wu, wd, bc=128, bh=128)
    r = moe_gemm_ref(x, wg, wu, wd)
    assert y.dtype == x.dtype
    jnp.allclose(y.astype(jnp.float32), r.astype(jnp.float32))
    import numpy as np
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(r, np.float32), **tol(dtype))


@pytest.mark.parametrize("B,S,H,Kv,D,win", [
    (2, 256, 4, 2, 64, None), (1, 128, 8, 8, 32, None),
    (2, 256, 4, 1, 64, 48), (1, 512, 2, 2, 128, None),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, S, H, Kv, D, win, dtype):
    import numpy as np
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Kv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Kv, D), dtype)
    y = flash_attention(q, k, v, causal=True, window=win, bq=64, bk=64)
    r = flash_attention_ref(q, k, v, causal=True, window=win)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(r, np.float32), **tol(dtype))


@pytest.mark.parametrize("B,C,H,Kv,D", [(2, 512, 8, 2, 64),
                                        (1, 1024, 4, 4, 32),
                                        (3, 512, 16, 1, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, C, H, Kv, D, dtype):
    import numpy as np
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Kv, C, D), dtype)
    v = jax.random.normal(ks[2], (B, Kv, C, D), dtype)
    lengths = jnp.asarray([(3 * C) // 4, 1, C][:B], jnp.int32)
    y = decode_attention(q, k, v, lengths, bc=128)
    r = decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(r, np.float32), **tol(dtype))


@pytest.mark.parametrize("B,S,W,bs,bw", [(2, 512, 512, 128, 128),
                                         (1, 1024, 256, 256, 256),
                                         (3, 256, 1024, 64, 512)])
def test_rg_lru(B, S, W, bs, bw):
    import numpy as np
    ks = jax.random.split(KEY, 3)
    a = jax.random.uniform(ks[0], (B, S, W), jnp.float32, 0.8, 0.999)
    b = jax.random.normal(ks[1], (B, S, W), jnp.float32) * 0.1
    h0 = jax.random.normal(ks[2], (B, W), jnp.float32)
    h, hl = rg_lru_scan(a, b, h0, bs=bs, bw=bw)
    hr, hlr = rg_lru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hlr),
                               rtol=1e-5, atol=1e-5)


def test_ragged_shapes_fall_back_to_ref():
    """Non-tiling shapes must still produce correct results (ref path)."""
    import numpy as np
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (2, 100, 96), jnp.float32)
    wg = jax.random.normal(ks[1], (2, 96, 100), jnp.float32) * 0.1
    wu = jax.random.normal(ks[2], (2, 96, 100), jnp.float32) * 0.1
    wd = jax.random.normal(ks[3], (2, 100, 96), jnp.float32) * 0.1
    y = moe_gemm(x, wg, wu, wd)
    r = moe_gemm_ref(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("B,H,S,D,bs", [(2, 2, 256, 64, 64),
                                        (1, 4, 128, 32, 128)])
def test_mlstm_scan_kernel(B, H, S, D, bs):
    import numpy as np
    from repro.kernels.mlstm_scan.ops import mlstm_scan
    from repro.kernels.mlstm_scan.ref import mlstm_scan_ref
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, H, S, D)) / (D ** 0.5)
    v = jax.random.normal(ks[2], (B, H, S, D))
    ig = jax.random.normal(ks[3], (B, H, S))
    lf = -jax.nn.softplus(-jax.random.normal(ks[4], (B, H, S)))
    C0 = jnp.zeros((B, H, D, D))
    n0 = jnp.zeros((B, H, D))
    m0 = jnp.full((B, H), -1e30)
    h1, C1, n1, m1 = mlstm_scan(q, k, v, ig, lf, C0, n0, m0, bs=bs)
    h2, C2, n2, m2 = mlstm_scan_ref(q, k, v, ig, lf, C0, n0, m0)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(C1), np.asarray(C2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), atol=1e-6)


def test_mlstm_block_kernel_path_matches_scan():
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.models import ssm as ssm_lib
    cfg = get_smoke_config("xlstm-1.3b")
    p = ssm_lib.mlstm_init(KEY, cfg)
    x = jax.random.normal(KEY, (2, 128, cfg.d_model), jnp.float32)
    y1, s1 = ssm_lib.mlstm_apply(p, cfg, x)
    y2, s2 = ssm_lib.mlstm_apply(p, cfg, x, use_kernel=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1["C"]), np.asarray(s2["C"]),
                               atol=1e-4)
