"""dedup_embedding_roofline: the gather's share of its memory roofline.

Bytes come from the algorithm: every id gathered reads one row of
``width`` float32 values and writes it once, ``2 * ids * width * 4``
bytes over the window.  The least time is those bytes over the chip's
HBM bandwidth (the bound: the gather does no arithmetic), and the time
taken is the device time of every op of the jitted gather program
(``jit_dedup_embedding``) in the traced window."""
from reduce import module_seconds

MODULE = "jit_dedup_embedding"


def gather_bytes(ids: int, width: int) -> int:
    return 2 * ids * width * 4


def read(run):
    t = module_seconds(run.trace.device, MODULE, run.lo, run.hi)
    ids = sum(run.gather_ids)
    if t <= 0 or not ids:
        return None
    return 100.0 * gather_bytes(ids, run.width) / run.peaks["hbm_bw"] / t
