"""req_p95_ms: 95th percentile, over every request answered in the
window, of send-to-answer-on-the-host time (host clock)."""
import numpy as np


def read(run):
    if not run.latencies_s:
        return None
    return float(np.percentile(run.latencies_s, 95)) * 1e3
