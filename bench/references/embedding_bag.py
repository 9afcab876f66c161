"""Plain reference of an embedding-bag classifier: mean-pooled token rows
through a linear head, in float64 on the host.

It imports nothing of the program.  The caller hands it the rows of a
request's tokens, taken from weights the benchmark regenerates from the
seed, and the head the benchmark made; it returns the logits and, per
logit, the sum of the magnitudes of the products that make it up (the
scale a float rounding error is measured against).
"""
from __future__ import annotations

import numpy as np


def logits(rows: np.ndarray, head: np.ndarray):
    """rows ``[docs, tokens, d]``, head ``[d, classes]`` ->
    ``(logits, scale)``, both ``[docs, classes]`` float64."""
    feats = np.asarray(rows, np.float64).mean(axis=1)
    head = np.asarray(head, np.float64)
    return feats @ head, np.abs(feats) @ np.abs(head)
