"""docs_per_s: documents answered in the window over the window's length,
from the callers' first send to the last answer (host clock)."""


def read(run):
    if not run.requests or run.window_s <= 0:
        return None
    return run.docs / run.window_s
