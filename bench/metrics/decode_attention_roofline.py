"""The Pallas decode attention kernel (``kernels/decode_attention``, named
``decode_attention``): the least time its traced calls need, each the
larger of required FLOPs over the bf16 peak and required bytes over the
HBM peak (every live row's K and V up to its length, its query and its
output; ``decode_attention_work``), over the summed device time of the
trace's ``decode_attention`` operations, in %. Moves ``tpot_mean_ms``.

Nothing is read unless the trace holds one kernel call per layer for
every traced step."""


def read(run):
    tr = run.trace
    if tr is None or not run.traced_steps:
        return None
    kernel_s = tr.kernel_s.get("decode_attention", 0.0)
    layers = int(run.cfg["num_hidden_layers"])
    steps = [s for s in run.traced_steps if s.keys]
    if kernel_s <= 0 or tr.kernel_count.get("decode_attention") != \
            layers * len(steps) * tr.devices:
        return None
    itemsize = 2
    least = 0.0
    for s in steps:
        flops, nbytes = run.model.decode_attention_work(run.cfg, s.keys,
                                                        itemsize)
        least += layers * max(flops / run.peaks["bf16_flops_per_s"],
                              nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
