"""engine_fetch_ms_per_req: self time of ``WeightServer.access_pages_
grouped`` (span ``bench.fetch``: buffer-pool bookkeeping, eviction and
page-set handling) less the storage and transfer spans inside it, in
host milliseconds per request answered in the traced window."""
from reduce import self_seconds
from spans import TRANSFER


def read(run):
    if not run.requests:
        return None
    s = self_seconds(run.trace.spans, "bench.fetch",
                     ("bench.storage_fetch",) + TRANSFER, run.lo, run.hi)
    return s * 1e3 / run.requests
