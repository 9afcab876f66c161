"""device_idle_share: percent of the traced window in which no operation
ran on the chip (1 - union of device op intervals / window)."""
from reduce import busy_seconds


def read(run):
    w = run.hi - run.lo
    if w <= 0:
        return None
    return 100.0 * (1.0 - busy_seconds(run.trace.device, run.lo, run.hi) / w)
