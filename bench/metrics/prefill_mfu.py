"""Prefill (``ServingEngine._prefill_group`` -> eager ``Model.prefill``):
the FLOPs that the prompt tokens prefilled in the window need (unpadded,
top-k experts only, causal attention, no output head: the first output
token comes from the decode step), over the summed wall time of the
engine's ``prefill_chunk`` histogram times the chips' bf16 peak, in %.
Moves ``ttft_p50_ms``."""


def read(run):
    if run.prefill_s <= 0:
        return None
    m = run.model
    flops = 0
    for s in run.served:
        t = s.req.admit_t
        if t is None or not run.in_window(t):
            continue
        n = s.prompt_len - 1                 # the last one goes to decode
        flops += n * m.token_flops(run.cfg, head=False)
        flops += m.attention_flops(run.cfg, n * (n + 1) // 2)
    peak = run.peaks["bf16_flops_per_s"] * run.chips
    return 100.0 * flops / (run.prefill_s * peak)
