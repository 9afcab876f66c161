"""transfer_ms_per_req: host milliseconds inside the transfer engine's
``stage`` and ``load_group`` and per-page loads (host side of the
host-to-HBM movement; the copies themselves run asynchronously), less
the store backend's reads inside them (``bench.storage_fetch``, which
``storage_fetch_ms_per_req`` reads), per request answered in the traced
window."""
from reduce import self_seconds
from spans import TRANSFER


def read(run):
    if not run.requests:
        return None
    s = self_seconds(run.trace.spans, TRANSFER, ("bench.storage_fetch",),
                     run.lo, run.hi)
    return s * 1e3 / run.requests
