"""End to end: the time per output token over all the window's decoding:
every gap between two consecutive output tokens of one request, both
inside the window, summed over all requests and divided by the number of
such gaps, in ms."""


def read(run):
    total, gaps = 0.0, 0
    for s in run.served:
        ts = [t for t in s.token_t if run.in_window(t)]
        if len(ts) >= 2:
            total += ts[-1] - ts[0]
            gaps += len(ts) - 1
    return 1e3 * total / gaps if gaps else None
