"""The system under test for a ``moe_transformer`` configuration: the
repository's ``ServingEngine`` built from a configuration file.

This is the only module of the benchmark that imports the program. It maps
the file's Hugging Face keys (as served: ``served_keys``) onto the
program's ``ModelConfig``, hands the engine the weights the benchmark
made, and gives the harness what it drives and reads through ``System``:
requests in, steps, the engine's own step timings and phase spans. It adds
nothing to the serving path.
"""
from __future__ import annotations

import math
import os
import sys
from typing import List, Tuple

# the program lives in the checkout's src/
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "src"))


def served_keys(cfg: dict) -> dict:
    """The file's keys with each stated departure's served value."""
    out = dict(cfg)
    out.update({k: d["served"]
                for k, d in cfg.get("departures", {}).items()})
    return out


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig, MoEConfig
    serving = cfg["serving"]
    cfg = served_keys(cfg)
    E = int(cfg.get("num_experts") or cfg["num_local_experts"])
    shared = int(cfg.get("shared_expert_intermediate_size") or 0)
    H = int(cfg["num_attention_heads"])
    D = int(cfg.get("head_dim") or cfg["hidden_size"] // H)
    # the program scales attention by 1/sqrt(head size) and applies no
    # embedding, residual or logits multiplier; the file must say so
    for key, served in (("attention_multiplier", 1.0 / math.sqrt(D)),
                        ("embedding_multiplier", 1.0),
                        ("residual_multiplier", 1.0),
                        ("logits_scaling", 1.0)):
        if key in cfg and not math.isclose(float(cfg[key]), served):
            raise ValueError(f"{cfg['name']}: {key}={cfg[key]} is not what "
                             f"the serving engine computes ({served})")
    if not cfg.get("norm_topk_prob", True) or cfg.get("shared_expert_gate"):
        raise ValueError(f"{cfg['name']}: the serving engine renormalizes "
                         "the top-k weights and gates no shared expert")
    return ModelConfig(
        name=cfg["name"], family="moe",
        num_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]), num_heads=H,
        num_kv_heads=int(cfg["num_key_value_heads"]), head_dim=D,
        ffn_dim=0, vocab_size=int(cfg["vocab_size"]), attention="full",
        rope_theta=float(cfg["rope_theta"]),
        qkv_bias=bool(cfg.get("qkv_bias", False)),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        norm_eps=float(cfg["rms_norm_eps"]),
        moe=MoEConfig(
            num_experts=E, top_k=int(cfg["num_experts_per_tok"]),
            expert_ffn_dim=int(cfg.get("moe_intermediate_size")
                               or cfg["intermediate_size"]),
            num_shared_experts=1 if shared else 0, shared_ffn_dim=shared,
            capacity_factor=float(serving["capacity_factor"])),
        source=cfg["source"])


def prefill_groups(cfg: dict, lo: int, hi: int
                   ) -> List[Tuple[int, int, int]]:
    """(bucket, rows, prompt tokens) of every prefill group the engine can
    form from prompts of ``lo`` to ``hi`` tokens under the file's slots and
    token budget: for each padded length those prompts fall in, every group
    size from 1 to the most that fit the budget at the shortest prompt of
    that bucket. The last prompt token goes to the decode step, so a prompt
    of n tokens prefills n - 1."""
    from repro.sched.occupancy import bucket_length
    serving = cfg["serving"]
    slots = int(serving["num_slots"])
    ctx = int(serving["max_context"])
    budget = serving.get("token_budget")
    budget = None if budget is None else int(budget)
    lo, hi = max(lo - 1, 1), hi - 1
    out, prev = [], 0
    b = bucket_length(lo)
    while True:
        b = min(b, ctx)
        shortest = max(lo, prev + 1)
        if shortest <= min(hi, b):
            most = slots if budget is None else max(1, budget // shortest)
            out += [(b, n, shortest + 1)
                    for n in range(1, min(slots, most) + 1)]
        if b >= min(hi, ctx):
            return out
        prev, b = b, bucket_length(b + 1)


class System:
    """A ``ServingEngine`` over ``params`` with the file's serving
    settings: slots, context, admission and its token budget, the decode
    kernel. Raises where the program cannot serve the file as stated."""

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

    def submit(self, spec, due: float):
        """Hand the engine one request due at ``due`` (host clock). The
        request it returns fills ``output`` as tokens are made and sets
        ``admit_t``, ``finish_t`` or ``error``."""
        from repro.runtime import Request
        req = Request(prompt=spec.prompt.tolist(),
                      max_new_tokens=spec.max_new_tokens,
                      temperature=spec.temperature, top_k=spec.top_k,
                      arrival_t=due)
        self.engine.submit(req)
        return req

    def busy(self) -> bool:
        e = self.engine
        return bool(e.waiting) or any(r is not None for r in e.slots)

    def step(self) -> None:
        self.engine.step()

    def record_phases(self) -> None:
        """From now on keep the engine's phase spans (``admit``,
        ``prefill_chunk``, ``decode_step``) on the host clock."""
        from repro.obs.trace import TraceRecorder
        self.engine.tracer = TraceRecorder()

    def phases(self) -> List[Tuple[str, float, float]]:
        tr = self.engine.tracer
        return [] if tr is None else [(s.name, s.start, s.end)
                                      for s in tr.spans if s.cat == "phase"]

    def reset_stats(self) -> None:
        self.engine.reset_stats()

    def timings(self) -> dict:
        """The engine's own fenced timings since ``reset_stats``: summed
        seconds of its decode steps and their count, and summed seconds of
        its prefill chunks."""
        h = self.engine.metrics.histogram
        dec = h("repro_engine_decode_step_seconds")
        return {"decode_s": dec.sum, "decode_n": dec.count,
                "prefill_s": h("repro_engine_prefill_chunk_seconds").sum}

    def free(self) -> None:
        """Drop the engine's cache and close it; the weights stay with
        whoever made them."""
        self.engine.kv.caches = None
        self.engine.close()
        self.engine = None
