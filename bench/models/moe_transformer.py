"""Plain float32 reference of a decoder-only mixture-of-experts transformer,
with the weights the benchmark serves and the work it counts.

Independent of the system under test: this module imports nothing of
``repro``. Everything it needs comes from a configuration file under
``bench/configs`` (Hugging Face key names, as served).

* ``make_params``: the served weights, made on the device from the seed in
  one jitted call, in the serving dtype. The tree is the layout the serving
  engine takes (``ServingEngine(params=...)``).
* ``reference_logits``: one unbatched sequence in, float32 logits out. No
  kernel, cache, batching or capacity buffer; every expert is computed for
  every token and weighted by the router's top-k. Matrix products run at
  ``highest`` precision, since a TPU otherwise multiplies float32 in bf16
  passes. ``weight_dtype`` rounds every weight to a lower precision first
  (the precision control), by ``round_to``.
* ``weight_bytes``, ``token_flops``, ``attention_flops``,
  ``decode_attention_work``: the work the model needs, from its shapes.

The layer equations (pre-norm RMSNorm, rotate-half RoPE, SwiGLU experts,
softmax router over the routed experts) follow the Qwen2-MoE and
GraniteMoE descriptions. The Granite multipliers (embedding, attention,
residual, logits) and Qwen's ``norm_topk_prob`` are read from the file,
with the served value of each departure the file states
(``departures``); Qwen's sigmoid gate on the shared expert is applied
only where ``shared_expert_gate`` is served.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float8_e4m3fn": jnp.float8_e4m3fn}


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------

def served_keys(cfg: dict) -> dict:
    """The file's keys with each stated departure's served value: what
    the reference computes."""
    out = dict(cfg)
    out.update({k: d["served"]
                for k, d in cfg.get("departures", {}).items()})
    return out


class Shapes:
    """The sizes one configuration file states, under short names, as
    served."""

    def __init__(self, cfg: dict):
        cfg = served_keys(cfg)
        self.M = int(cfg["hidden_size"])
        self.L = int(cfg["num_hidden_layers"])
        self.H = int(cfg["num_attention_heads"])
        self.Kv = int(cfg["num_key_value_heads"])
        self.D = int(cfg.get("head_dim") or self.M // self.H)
        self.V = int(cfg["vocab_size"])
        self.E = int(cfg.get("num_experts") or cfg["num_local_experts"])
        self.k = int(cfg["num_experts_per_tok"])
        self.F = int(cfg.get("moe_intermediate_size")
                     or cfg["intermediate_size"])
        self.Fs = int(cfg.get("shared_expert_intermediate_size") or 0)
        self.qkv_bias = bool(cfg.get("qkv_bias", False))
        self.tied = bool(cfg.get("tie_word_embeddings", False))
        self.eps = float(cfg["rms_norm_eps"])
        self.theta = float(cfg["rope_theta"])
        self.norm_topk = bool(cfg.get("norm_topk_prob", True))
        self.shared_gate = bool(cfg.get("shared_expert_gate", False))
        self.emb_mult = float(cfg.get("embedding_multiplier", 1.0))
        self.res_mult = float(cfg.get("residual_multiplier", 1.0))
        self.attn_mult = float(cfg.get("attention_multiplier",
                                       1.0 / math.sqrt(self.D)))
        self.logits_scaling = float(cfg.get("logits_scaling", 1.0))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def param_shapes(cfg: dict) -> dict:
    """{path: (shape, init scale)} of every weight; scale 0 is a norm
    scale (ones), a negative scale a bias (normal with that |scale|)."""
    s = Shapes(cfg)
    M, H, Kv, D, E, F = s.M, s.H, s.Kv, s.D, s.E, s.F
    out = {("embed", "embedding"): ((s.V, M), 1.0 / math.sqrt(M)),
           ("final_norm", "scale"): ((M,), 0.0)}
    if not s.tied:
        out[("lm_head", "kernel")] = ((M, s.V), 1.0 / math.sqrt(M))
    for i in range(s.L):
        p = ("layers", i)
        out[p + ("ln1", "scale")] = ((M,), 0.0)
        out[p + ("ln2", "scale")] = ((M,), 0.0)
        for name, width in (("wq", H * D), ("wk", Kv * D), ("wv", Kv * D)):
            out[p + ("attn", name, "kernel")] = ((M, width),
                                                 1.0 / math.sqrt(M))
            if s.qkv_bias:
                out[p + ("attn", name, "bias")] = ((width,), -0.02)
        out[p + ("attn", "wo", "kernel")] = ((H * D, M),
                                             1.0 / math.sqrt(H * D))
        out[p + ("moe", "router", "kernel")] = ((M, E), 1.0 / math.sqrt(M))
        out[p + ("moe", "experts", "gate")] = ((E, M, F), 1.0 / math.sqrt(M))
        out[p + ("moe", "experts", "up")] = ((E, M, F), 1.0 / math.sqrt(M))
        out[p + ("moe", "experts", "down")] = ((E, F, M), 1.0 / math.sqrt(F))
        if s.Fs:
            out[p + ("moe", "shared", "gate", "kernel")] = (
                (M, s.Fs), 1.0 / math.sqrt(M))
            out[p + ("moe", "shared", "up", "kernel")] = (
                (M, s.Fs), 1.0 / math.sqrt(M))
            out[p + ("moe", "shared", "down", "kernel")] = (
                (s.Fs, M), 1.0 / math.sqrt(s.Fs))
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = leaf
    tree["layers"] = [tree["layers"][i] for i in sorted(tree["layers"])]
    return tree


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Every weight, drawn from ``seed`` on the default device in one
    jitted call and held in ``dtype``. Norm scales are drawn near one and
    biases small, so that every term of the layer equations is exercised."""
    shapes = param_shapes(cfg)
    paths = sorted(shapes, key=str)

    def make(key):
        flat = {}
        for i, path in enumerate(paths):
            shape, scale = shapes[path]
            k = jax.random.fold_in(key, i)
            x = jax.random.normal(k, shape, jnp.float32)
            if scale == 0.0:
                x = 1.0 + 0.1 * x
            else:
                x = abs(scale) * x
            flat[path] = x.astype(dtype)
        return _nest(flat)

    # a seed of any size: fold the high and low 32 bits into the key
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
    the projections, the router, its top-k routed experts, the shared
    expert and, with ``head``, the output head. Experts it is not routed
    to do not count, nor does any padding."""
    s = Shapes(cfg)
    per_layer = (s.M * (s.H + 2 * s.Kv) * s.D + s.H * s.D * s.M
                 + s.M * s.E + s.k * 3 * s.M * s.F + 3 * s.M * s.Fs)
    return 2 * (s.L * per_layer + (s.M * s.V if head else 0))


def attention_flops(cfg: dict, keys: int) -> int:
    """FLOPs of one query token's scores and weighted values over ``keys``
    cached positions, summed over the layers."""
    s = Shapes(cfg)
    return 4 * s.L * s.H * s.D * int(keys)


def decode_attention_work(cfg: dict, keys, itemsize: int = 2):
    """(FLOPs, bytes) one layer's decode attention needs for rows that
    attend ``keys`` positions each: every live row's K and V up to its
    length, its query and its output."""
    s = Shapes(cfg)
    keys = np.asarray(keys, np.int64)
    flops = int(4 * s.H * s.D * keys.sum())
    nbytes = int(itemsize * (2 * s.Kv * s.D * keys.sum()
                             + 2 * len(keys) * s.H * s.D))
    return flops, nbytes


# ---------------------------------------------------------------------------
# reference forward
# ---------------------------------------------------------------------------

def round_to(x, dtype):
    """``x`` (float32) rounded to the nearest value of the floating type
    ``dtype`` (round half to even, saturating at its largest finite value),
    in float32 and integer arithmetic. A compiler may fold or widen a
    round trip through a narrow type (``x.astype(f8).astype(f32)``) on a
    chip that has no such type; this arithmetic it cannot skip."""
    fi = jnp.finfo(dtype)
    shift = 23 - int(fi.nmant)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    lsb = (bits >> shift) & jnp.uint32(1)
    bits = (bits + jnp.uint32((1 << (shift - 1)) - 1) + lsb) \
        & jnp.uint32((0xFFFFFFFF << shift) & 0xFFFFFFFF)
    y = jax.lax.bitcast_convert_type(bits, jnp.float32)
    tiny = float(fi.smallest_normal)
    step = tiny * 2.0 ** -int(fi.nmant)        # the subnormals' spacing
    y = jnp.where(jnp.abs(x) < tiny, jnp.round(x / step) * step, y)
    return jnp.clip(y, -float(fi.max), float(fi.max))


def _f32(tree, weight_dtype=None):
    def cast(a):
        a = jnp.asarray(a, jnp.float32)
        return a if weight_dtype is None else round_to(a, weight_dtype)
    return jax.tree.map(cast, tree)


def _linear(p, x):
    y = x @ p["kernel"]
    return y + p["bias"] if "bias" in p else y


def _rmsnorm(p, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def _rope(x, pos, theta):
    """x: [S, heads, D]; rotate-half RoPE at absolute positions ``pos``."""
    D = x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float32) / D)
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _attention(p, s: Shapes, h):
    S = h.shape[0]
    pos = jnp.arange(S)
    q = _rope(_linear(p["wq"], h).reshape(S, s.H, s.D), pos, s.theta)
    k = _rope(_linear(p["wk"], h).reshape(S, s.Kv, s.D), pos, s.theta)
    v = _linear(p["wv"], h).reshape(S, s.Kv, s.D)
    k = jnp.repeat(k, s.H // s.Kv, axis=1)          # head i reads KV head
    v = jnp.repeat(v, s.H // s.Kv, axis=1)          # i // (H / Kv)
    logits = jnp.einsum("shd,thd->hst", q, k) * s.attn_mult
    causal = pos[:, None] >= pos[None, :]
    probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    out = jnp.einsum("hst,thd->shd", probs, v).reshape(S, s.H * s.D)
    return _linear(p["wo"], out)


def _swiglu(p, x):
    return _linear(p["down"], jax.nn.silu(_linear(p["gate"], x))
                   * _linear(p["up"], x))


def _moe(p, s: Shapes, h, rows: int = 256):
    """Every token through its top-k experts, exactly: all experts are
    computed for a block of ``rows`` tokens at a time and combined with the
    router's weights (zero for the experts not chosen)."""
    T = h.shape[0]
    probs = jax.nn.softmax(_linear(p["router"], h), -1)
    w, idx = jax.lax.top_k(probs, s.k)
    if s.norm_topk:
        w = w / w.sum(-1, keepdims=True)
    combine = jnp.zeros((T, s.E)).at[jnp.arange(T)[:, None], idx].add(w)
    ex = p["experts"]
    pad = -T % rows
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, rows, s.M)
    cb = jnp.pad(combine, ((0, pad), (0, 0))).reshape(-1, rows, s.E)

    def block(args):
        x, c = args
        act = (jax.nn.silu(jnp.einsum("tm,emh->teh", x, ex["gate"]))
               * jnp.einsum("tm,emh->teh", x, ex["up"]))
        return jnp.einsum("teh,ehm->tm", act * c[:, :, None], ex["down"])

    y = jax.lax.map(block, (hb, cb)).reshape(-1, s.M)[:T]
    if "shared" in p:
        shared = _swiglu(p["shared"], h)
        if s.shared_gate:
            shared = jax.nn.sigmoid(_linear(p["shared_gate"], h)) * shared
        y = y + shared
    return y


@partial(jax.jit, static_argnums=(1, 3))
def _block(p, skey, x, weight_dtype):
    s = Shapes(dict(skey))
    p = _f32(p, weight_dtype)
    a = _attention(p["attn"], s, _rmsnorm(p["ln1"], x, s.eps))
    x = x + s.res_mult * a
    y = _moe(p["moe"], s, _rmsnorm(p["ln2"], x, s.eps))
    return x + s.res_mult * y


@partial(jax.jit, static_argnums=(2, 3))
def _embed(table, tokens, mult, weight_dtype):
    return _f32(table, weight_dtype)[tokens] * mult


@partial(jax.jit, static_argnums=(4, 5, 6))
def _head(norm, w, x, rows, eps, scaling, weight_dtype):
    x = _rmsnorm(_f32(norm, weight_dtype), x[rows], eps)
    return (x @ _f32(w, weight_dtype)) / scaling


def _shape_key(cfg: dict):
    """A hashable copy of the file's numbers that ``Shapes`` reads."""
    keys = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "vocab_size", "num_experts",
            "num_local_experts", "num_experts_per_tok",
            "moe_intermediate_size", "intermediate_size",
            "shared_expert_intermediate_size", "qkv_bias",
            "tie_word_embeddings", "rms_norm_eps", "rope_theta",
            "norm_topk_prob", "shared_expert_gate", "embedding_multiplier",
            "residual_multiplier", "attention_multiplier", "logits_scaling")
    cfg = served_keys(cfg)
    return tuple((k, cfg[k]) for k in keys if k in cfg)


def reference_logits(params, cfg: dict, tokens, rows, *, pad_to: int = 512,
                     weight_dtype=None):
    """Float32 logits [len(rows), V] at positions ``rows`` of one
    unbatched token sequence. The sequence is padded at its end to a
    multiple of ``pad_to``, which changes no earlier position under the
    causal mask, so that few lengths compile. One jit per layer, so at most
    one layer's float32 weights are live at a time."""
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
        x = _embed(params["embed"]["embedding"], jnp.asarray(padded),
                   s.emb_mult, weight_dtype)
        for layer in params["layers"]:
            x = _block(layer, skey, x, weight_dtype)
        w = (params["embed"]["embedding"].T if s.tied
             else params["lm_head"]["kernel"])
        out = _head(params["final_norm"], w, x, jnp.asarray(rows_p),
                    s.eps, s.logits_scaling, weight_dtype)
    return out[:len(rows)]
