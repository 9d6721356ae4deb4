"""The Pallas decode attention kernel (``kernels/decode_attention``, named
``decode_attention``, in its GQA or its latent MLA mode): the summed
device time of the trace's ``decode_attention`` operations per traced
decode step, averaged over the chips, in ms. Moves ``tpot_mean_ms``.

Nothing is read unless the trace holds one kernel call per layer for
every traced step."""


def read(run):
    tr = run.trace
    if tr is None or not run.traced_steps:
        return None
    steps = [s for s in run.traced_steps if s.keys]
    kernel_s = tr.kernel_s.get("decode_attention", 0.0)
    layers = int(run.cfg["num_hidden_layers"])
    if not steps or kernel_s <= 0 or tr.kernel_count.get(
            "decode_attention") != layers * len(steps) * tr.devices:
        return None
    return 1e3 * kernel_s / len(steps)
