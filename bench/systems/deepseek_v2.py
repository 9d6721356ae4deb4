"""The system under test for a ``deepseek_v2`` configuration: the
repository's ``ServingEngine`` serving DeepSeek-V2's block (MLA with YaRN
rope, a dense first layer, routed and shared experts).

Maps the file's Hugging Face keys onto the program's ``ModelConfig``; the
rest (the prefill group shapes, the harness's ``System``) is
``moe_transformer.py``'s, loaded by path. A checkout whose program has no
YaRN scaling (no ``YarnScaling``) cannot serve this block: the import
below fails, and the harness reports it before any weight is made.
"""
from __future__ import annotations

import os
import sys


_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))

from harness import load_module  # noqa: E402

_mt = load_module(os.path.join(_HERE, "moe_transformer.py"),
                  "bench_system_moe_transformer")

from repro.configs.base import YarnScaling  # noqa: E402

prefill_groups = _mt.prefill_groups


def model_config(cfg: dict):
    """The file's keys as the program's ``ModelConfig``. Raises where the
    file states something the program does not compute."""
    from repro.configs.base import ModelConfig, MoEConfig
    for key, want in (("q_lora_rank", None), ("scoring_func", "softmax"),
                      ("topk_method", "greedy"), ("n_group", 1),
                      ("topk_group", 1), ("routed_scaling_factor", 1),
                      ("moe_layer_freq", 1), ("hidden_act", "silu"),
                      ("attention_bias", False)):
        if cfg.get(key, want) != want:
            raise ValueError(f"{cfg['name']}: {key}={cfg[key]!r} is not "
                             f"what the serving engine computes ({want!r})")
    y = cfg["rope_scaling"]
    if y.get("type") != "yarn":
        raise ValueError(f"{cfg['name']}: rope_scaling {y!r}: the serving "
                         "engine computes YaRN only")
    serving = cfg["serving"]
    H = int(cfg["num_attention_heads"])
    return ModelConfig(
        name=cfg["name"], family="moe",
        num_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]), num_heads=H,
        num_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["qk_nope_head_dim"]),
        ffn_dim=int(cfg["intermediate_size"]),
        vocab_size=int(cfg["vocab_size"]), attention="mla",
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=YarnScaling(
            factor=float(y["factor"]),
            original_max_position=int(y["original_max_position_embeddings"]),
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"])),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        norm_eps=float(cfg["rms_norm_eps"]),
        mla_kv_lora_rank=int(cfg["kv_lora_rank"]),
        mla_rope_dim=int(cfg["qk_rope_head_dim"]),
        mla_v_dim=int(cfg["v_head_dim"]),
        moe=MoEConfig(
            num_experts=int(cfg["n_routed_experts"]),
            top_k=int(cfg["num_experts_per_tok"]),
            expert_ffn_dim=int(cfg["moe_intermediate_size"]),
            num_shared_experts=int(cfg.get("n_shared_experts") or 0),
            shared_ffn_dim=int(cfg["moe_intermediate_size"]),
            capacity_factor=float(serving["capacity_factor"]),
            moe_layer_start=int(cfg.get("first_k_dense_replace", 0)),
            norm_topk_prob=bool(cfg["norm_topk_prob"])),
        source=cfg["source"])


class System(_mt.System):
    """``moe_transformer``'s ``System`` over this family's
    ``ModelConfig``."""

    def __init__(self, cfg: dict, params, *, dtype, seed: int):
        from repro.runtime import ServingEngine
        serving = cfg["serving"]
        self.engine = ServingEngine(
            model_config(cfg), params,
            num_slots=int(serving["num_slots"]),
            max_context=int(serving["max_context"]),
            admission=serving["admission"],
            token_budget=serving.get("token_budget"),
            attn_impl=serving.get("attn_impl", "decode_kernel"),
            dtype=dtype, seed=seed & 0x7FFFFFFF)
