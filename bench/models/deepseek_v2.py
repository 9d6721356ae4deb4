"""Plain float32 reference of DeepSeek-V2 (MLA attention, a dense first
layer, routed and shared experts), with the weights the benchmark serves
and the work it counts.

Independent of the system under test: this module imports nothing of
``repro``. Everything it needs comes from a configuration file under
``bench/configs`` (Hugging Face key names, as published).

The layer equations follow DeepSeek-V2's modeling code:

* attention (MLA, no query compression): per head q = [q_nope ‖ q_pe];
  [c ‖ k_pe] = x·W_kv_a; c_kv = RMSNorm(c); k_pe is one rope key all heads
  share; [k_nope ‖ v] = c_kv·W_kv_b per head; scores (q_nope·k_nope +
  rope(q_pe)·rope(k_pe)) · mscale² / sqrt(dn + dr), where mscale is YaRN's
  for ``rope_scaling.mscale_all_dim``; the rope is YaRN's (frequencies
  blended over the correction range, cos and sin scaled by
  mscale(factor, mscale) / mscale(factor, mscale_all_dim)), on
  rotate-half pairs;
* the first ``first_k_dense_replace`` layers a SwiGLU of
  ``intermediate_size``, the rest a softmax router over
  ``n_routed_experts``, top-k (renormalized only with
  ``norm_topk_prob``), each expert a SwiGLU of ``moe_intermediate_size``,
  plus the shared experts as one SwiGLU of ``n_shared_experts`` times
  that width.

The reference expands K and V for every position (it never uses the
latent cache), computes every expert on every token, and runs attention
in blocks of query rows so that sequences of several thousand tokens fit
on one chip. Matrix products run at ``highest`` precision.
``weight_dtype`` rounds every weight first (the precision control).

The equations that are the same as ``moe_transformer.py``'s (RMSNorm,
SwiGLU, the router and its experts, rounding, the weights' tree) are that
module's, loaded by path.
"""
from __future__ import annotations

import math
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))

from harness import load_module  # noqa: E402

_mt = load_module(os.path.join(_HERE, "moe_transformer.py"),
                  "bench_model_moe_transformer")

DTYPES = _mt.DTYPES
round_to = _mt.round_to


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------

class Shapes:
    """The sizes one configuration file states, under short names."""

    def __init__(self, cfg: dict):
        for key, want in (("q_lora_rank", None), ("scoring_func", "softmax"),
                          ("topk_method", "greedy"), ("n_group", 1),
                          ("topk_group", 1), ("routed_scaling_factor", 1),
                          ("moe_layer_freq", 1), ("hidden_act", "silu"),
                          ("attention_bias", False)):
            if cfg.get(key, want) != want:
                raise ValueError(f"{key}={cfg[key]!r}: the reference "
                                 f"computes {want!r}")
        self.M = int(cfg["hidden_size"])
        self.L = int(cfg["num_hidden_layers"])
        self.H = int(cfg["num_attention_heads"])
        self.r = int(cfg["kv_lora_rank"])
        self.dn = int(cfg["qk_nope_head_dim"])
        self.dr = int(cfg["qk_rope_head_dim"])
        self.dv = int(cfg["v_head_dim"])
        self.V = int(cfg["vocab_size"])
        self.E = int(cfg["n_routed_experts"])
        self.k = int(cfg["num_experts_per_tok"])
        self.F = int(cfg["moe_intermediate_size"])
        self.Fs = self.F * int(cfg.get("n_shared_experts") or 0)
        self.Fd = int(cfg["intermediate_size"])
        self.dense = int(cfg.get("first_k_dense_replace", 0))
        self.tied = bool(cfg.get("tie_word_embeddings", False))
        self.eps = float(cfg["rms_norm_eps"])
        self.theta = float(cfg["rope_theta"])
        self.norm_topk = bool(cfg.get("norm_topk_prob", False))
        self.shared_gate = False
        self.yarn = cfg.get("rope_scaling")
        if self.yarn is not None and self.yarn.get("type") != "yarn":
            raise ValueError(f"rope_scaling {self.yarn!r}: the reference "
                             "computes YaRN only")

    def is_dense(self, layer: int) -> bool:
        return layer < self.dense

    def scale(self) -> float:
        s = 1.0 / math.sqrt(self.dn + self.dr)
        if self.yarn and self.yarn.get("mscale_all_dim"):
            s *= _mscale(float(self.yarn["factor"]),
                         float(self.yarn["mscale_all_dim"])) ** 2
        return s


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def param_shapes(cfg: dict) -> dict:
    """{path: (shape, init scale)} of every weight, in the serving
    engine's tree; scale 0 is a norm scale (ones)."""
    s = Shapes(cfg)
    M, H = s.M, s.H
    out = {("embed", "embedding"): ((s.V, M), 1.0 / math.sqrt(M)),
           ("final_norm", "scale"): ((M,), 0.0)}
    if not s.tied:
        out[("lm_head", "kernel")] = ((M, s.V), 1.0 / math.sqrt(M))

    def swiglu(prefix, width):
        out[prefix + ("gate", "kernel")] = ((M, width), 1.0 / math.sqrt(M))
        out[prefix + ("up", "kernel")] = ((M, width), 1.0 / math.sqrt(M))
        out[prefix + ("down", "kernel")] = ((width, M),
                                            1.0 / math.sqrt(width))

    for i in range(s.L):
        p = ("layers", i)
        out[p + ("ln1", "scale")] = ((M,), 0.0)
        out[p + ("ln2", "scale")] = ((M,), 0.0)
        a = p + ("attn",)
        out[a + ("wq", "kernel")] = ((M, H * (s.dn + s.dr)),
                                     1.0 / math.sqrt(M))
        out[a + ("w_kv_a", "kernel")] = ((M, s.r + s.dr), 1.0 / math.sqrt(M))
        out[a + ("kv_norm", "scale")] = ((s.r,), 0.0)
        out[a + ("w_kv_b", "kernel")] = ((s.r, H * (s.dn + s.dv)),
                                         1.0 / math.sqrt(s.r))
        out[a + ("wo", "kernel")] = ((H * s.dv, M), 1.0 / math.sqrt(H * s.dv))
        if s.is_dense(i):
            swiglu(p + ("mlp",), s.Fd)
            continue
        m = p + ("moe",)
        out[m + ("router", "kernel")] = ((M, s.E), 1.0 / math.sqrt(M))
        out[m + ("experts", "gate")] = ((s.E, M, s.F), 1.0 / math.sqrt(M))
        out[m + ("experts", "up")] = ((s.E, M, s.F), 1.0 / math.sqrt(M))
        out[m + ("experts", "down")] = ((s.E, s.F, M), 1.0 / math.sqrt(s.F))
        if s.Fs:
            swiglu(m + ("shared",), s.Fs)
    return out


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Every weight, drawn from ``seed`` on the default device in one
    jitted call and held in ``dtype``; norm scales near one."""
    shapes = param_shapes(cfg)
    paths = sorted(shapes, key=str)

    def make(key):
        flat = {}
        for i, path in enumerate(paths):
            shape, scale = shapes[path]
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            x = 1.0 + 0.1 * x if scale == 0.0 else scale * x
            flat[path] = x.astype(dtype)
        return _mt._nest(flat)

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0xFFFFFFFF)
    return jax.jit(make)(key)


def weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    return itemsize * sum(int(np.prod(shape))
                          for shape, _ in param_shapes(cfg).values())


# ---------------------------------------------------------------------------
# required work
# ---------------------------------------------------------------------------

def token_flops(cfg: dict, head: bool = True) -> int:
    """FLOPs one token needs outside attention's score and value products:
    the attention projections (W_q, W_kv_a, W_kv_b, W_o), the dense
    layer's SwiGLU, the router, its top-k routed experts, the shared
    experts and, with ``head``, the output head. (The absorbed decode's
    q·W_uk and ·W_uv are W_kv_b's products per token.)"""
    s = Shapes(cfg)
    attn = (s.M * s.H * (s.dn + s.dr) + s.M * (s.r + s.dr)
            + s.r * s.H * (s.dn + s.dv) + s.H * s.dv * s.M)
    moe = s.M * s.E + s.k * 3 * s.M * s.F + 3 * s.M * s.Fs
    dense = 3 * s.M * s.Fd
    n_dense = min(s.dense, s.L)
    total = s.L * attn + n_dense * dense + (s.L - n_dense) * moe
    return 2 * (total + (s.M * s.V if head else 0))


def attention_flops(cfg: dict, keys: int) -> int:
    """FLOPs of one query token's scores and weighted values over ``keys``
    positions, summed over the layers, in the expanded arithmetic:
    dn + dr wide scores and dv wide values per head."""
    s = Shapes(cfg)
    return 2 * s.L * s.H * (s.dn + s.dr + s.dv) * int(keys)


def decode_attention_work(cfg: dict, keys, itemsize: int = 2):
    """(FLOPs, bytes) one layer's latent decode kernel needs for rows that
    attend ``keys`` positions each: each position's r + dr latent values
    read once (keys and values), each row's H absorbed queries (r + dr
    wide) and outputs (r wide); scores r + dr and values r wide per
    head."""
    s = Shapes(cfg)
    keys = np.asarray(keys, np.int64)
    w = s.r + s.dr
    flops = int(2 * s.H * (w + s.r) * keys.sum())
    nbytes = int(itemsize * (w * keys.sum() + len(keys) * s.H * (w + s.r)))
    return flops, nbytes


# ---------------------------------------------------------------------------
# reference forward
# ---------------------------------------------------------------------------

def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def _inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
    freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not yarn:
        return freq.astype(np.float32)
    factor = float(yarn["factor"])
    orig = float(yarn["original_max_position_embeddings"])

    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(
            theta))
    low = max(math.floor(corr_dim(float(yarn["beta_fast"]))), 0)
    high = min(math.ceil(corr_dim(float(yarn["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (freq / factor * ramp + freq * (1.0 - ramp)).astype(np.float32)


def _rope(x, pos, s: Shapes):
    """x: [S, heads, dr]; YaRN rotate-half RoPE at positions ``pos``."""
    D = x.shape[-1]
    ang = pos[:, None, None].astype(jnp.float32) * _inv_freq(D, s.theta,
                                                             s.yarn)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if s.yarn:
        m = (_mscale(float(s.yarn["factor"]), float(s.yarn["mscale"]))
             / _mscale(float(s.yarn["factor"]),
                       float(s.yarn["mscale_all_dim"])))
        cos, sin = cos * m, sin * m
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, s: Shapes, h, rows: int = 512):
    """MLA over one sequence, expanded: every position's K and V from
    c_kv·W_kv_b, causal softmax in blocks of ``rows`` query rows."""
    S = h.shape[0]
    pos = jnp.arange(S)
    q = (h @ p["wq"]["kernel"]).reshape(S, s.H, s.dn + s.dr)
    q = jnp.concatenate([q[..., :s.dn], _rope(q[..., s.dn:], pos, s)], -1)
    kv_a = h @ p["w_kv_a"]["kernel"]
    c = _mt._rmsnorm(p["kv_norm"], kv_a[:, :s.r], s.eps)
    k_pe = _rope(kv_a[:, None, s.r:], pos, s)                   # [S,1,dr]
    kv = (c @ p["w_kv_b"]["kernel"]).reshape(S, s.H, s.dn + s.dv)
    k = jnp.concatenate([kv[..., :s.dn],
                         jnp.broadcast_to(k_pe, (S, s.H, s.dr))], -1)
    v = kv[..., s.dn:]
    scale = s.scale()
    pad = -S % rows

    def block(args):
        qb, qpos = args                                  # [rows,H,D], [rows]
        logits = jnp.einsum("qhd,thd->hqt", qb, k) * scale
        causal = qpos[:, None] >= pos[None, :]
        probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), -1)
        return jnp.einsum("hqt,thd->qhd", probs, v)

    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, rows, s.H,
                                                         s.dn + s.dr)
    qpos = jnp.pad(pos, (0, pad)).reshape(-1, rows)
    out = jax.lax.map(block, (qb, qpos)).reshape(-1, s.H * s.dv)[:S]
    return out @ p["wo"]["kernel"]


@partial(jax.jit, static_argnums=(1, 2, 4))
def _block(p, skey, dense, x, weight_dtype):
    s = Shapes(_unkey(skey))
    p = _mt._f32(p, weight_dtype)
    x = x + _attention(p["attn"], s, _mt._rmsnorm(p["ln1"], x, s.eps))
    h = _mt._rmsnorm(p["ln2"], x, s.eps)
    if dense:
        return x + _mt._swiglu(p["mlp"], h)
    return x + _mt._moe(p["moe"], s, h)


def _shape_key(cfg: dict):
    """A hashable copy of the file's numbers that ``Shapes`` reads."""
    out = []
    for k in ("hidden_size", "num_hidden_layers", "num_attention_heads",
              "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
              "v_head_dim", "vocab_size", "n_routed_experts",
              "num_experts_per_tok", "moe_intermediate_size",
              "n_shared_experts", "intermediate_size",
              "first_k_dense_replace", "tie_word_embeddings",
              "rms_norm_eps", "rope_theta", "norm_topk_prob"):
        if k in cfg:
            out.append((k, cfg[k]))
    if cfg.get("rope_scaling"):
        out.append(("rope_scaling", tuple(sorted(
            cfg["rope_scaling"].items()))))
    return tuple(out)


def _unkey(skey) -> dict:
    d = dict(skey)
    if "rope_scaling" in d:
        d["rope_scaling"] = dict(d["rope_scaling"])
    return d


def reference_logits(params, cfg: dict, tokens, rows, *, pad_to: int = 512,
                     weight_dtype=None):
    """Float32 logits [len(rows), V] at positions ``rows`` of one
    unbatched token sequence, padded at its end to a multiple of
    ``pad_to`` (no earlier position changes under the causal mask). One
    jit per layer kind, so at most one layer's float32 weights are live."""
    s = Shapes(cfg)
    skey = _shape_key(cfg)
    n = len(tokens)
    padded = np.zeros(-(-n // pad_to) * pad_to, np.int32)
    padded[:n] = tokens
    rows = np.asarray(rows, np.int32)
    nrows = -(-len(rows) // 64) * 64
    rows_p = np.full(nrows, rows[-1] if len(rows) else 0, np.int32)
    rows_p[:len(rows)] = rows
    with jax.default_matmul_precision("highest"):
        x = _mt._embed(params["embed"]["embedding"], jnp.asarray(padded),
                       1.0, weight_dtype)
        for i, layer in enumerate(params["layers"]):
            x = _block(layer, skey, s.is_dense(i), x, weight_dtype)
        w = (params["embed"]["embedding"].T if s.tied
             else params["lm_head"]["kernel"])
        out = _mt._head(params["final_norm"], w, x, jnp.asarray(rows_p),
                        s.eps, 1.0, weight_dtype)
    return out[:len(rows)]
