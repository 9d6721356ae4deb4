"""Decode step (``ServingEngine.step`` -> the jitted ``_decode_step``):
the engine's ``decode_step`` histogram, summed wall time over the steps
in the window, in ms a step. Moves ``tpot_mean_ms``."""


def read(run):
    if run.decode_n == 0:
        return None
    return 1e3 * run.decode_s / run.decode_n
