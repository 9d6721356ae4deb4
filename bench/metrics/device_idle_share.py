"""The device: the share of the traced window (the last few seconds of
the window) in which no operation ran on it, averaged over the
chips, in %. Moves ``tpot_mean_ms``."""


def read(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
