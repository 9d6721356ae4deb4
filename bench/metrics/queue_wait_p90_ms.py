"""Admission and batching (``ServingEngine._admit`` over
``runtime/batching.py``): 90th percentile, over the requests due in the
window, of the wait from a request's due time to its admission
(``Request.admit_t``, stamped when its prefill is merged). Moves
``ttft_p50_ms``."""


def read(run):
    from harness import percentile
    waits = [(s.req.admit_t - s.due) * 1e3 for s in run.due_in_window()
             if s.req.admit_t is not None]
    return percentile(waits, 90)
