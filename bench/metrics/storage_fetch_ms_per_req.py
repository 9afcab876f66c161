"""storage_fetch_ms_per_req: host milliseconds inside the store backend's
``get_pages`` (span ``bench.storage_fetch``), per request answered in the
traced window."""
from reduce import span_seconds


def read(run):
    if not run.requests:
        return None
    s = span_seconds(run.trace.spans, "bench.storage_fetch", run.lo, run.hi)
    return s * 1e3 / run.requests
