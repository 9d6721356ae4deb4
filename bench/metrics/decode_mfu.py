"""The whole decode step's share of the chips' bf16 peak: the FLOPs every
output token emitted in the window needs (projections, router, its top-k
routed experts, the shared expert, attention over its actual context, the
output head; no padded capacity row), over the summed wall time of the
engine's ``decode_step`` histogram, in %. Moves ``tpot_mean_ms``."""


def read(run):
    if run.decode_s <= 0:
        return None
    m = run.model
    per_token = m.token_flops(run.cfg, head=True)
    flops = 0
    for step in run.steps:
        if not run.in_window(step.end):
            continue
        flops += len(step.keys) * per_token
        flops += m.attention_flops(run.cfg, sum(step.keys))
    peak = run.peaks["bf16_flops_per_s"] * run.chips
    return 100.0 * flops / (run.decode_s * peak)
