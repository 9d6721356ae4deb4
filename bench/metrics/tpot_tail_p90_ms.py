"""The serving loop's tail of the time per output token: the 90th
percentile, over requests with two or more output tokens inside the
window, of each one's mean gap between them. Moves ``tpot_mean_ms``."""


def read(run):
    from harness import percentile
    vals = []
    for s in run.served:
        ts = [t for t in s.token_t if run.in_window(t)]
        if len(ts) >= 2:
            vals.append((ts[-1] - ts[0]) / (len(ts) - 1) * 1e3)
    return percentile(vals, 90)
