"""End to end: the median, over every request due in the window, of the
time from a request's due time to its first output token (stamped when
the engine step that made it returns). A request due in the window is
served to its first token even after the window closes; one that never
gets it counts as beyond every limit."""
import math


def read(run):
    from harness import percentile
    return percentile([(s.token_t[0] - s.due) * 1e3 if s.token_t
                       else math.inf for s in run.due_in_window()], 50)
