"""The serving loop's tail of the time to the first token: the 90th
percentile, over the requests due in the window, of the time from a
request's due time to its first output token (stamped when the engine
step that made it returns). A request due in the window is served to its
first token even after the window closes; one that never gets it counts
as beyond every limit. Moves ``ttft_p50_ms``. (About five requests lie
beyond it in a window of 49, so it spreads too widely from run to run to
stand as an end-to-end metric.)"""
import math


def read(run):
    from harness import percentile
    return percentile([(s.token_t[0] - s.due) * 1e3 if s.token_t
                       else math.inf for s in run.due_in_window()], 90)
