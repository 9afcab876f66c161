"""How ``correct`` is decided: served logits against the plain reference.

After the window has closed and the program's state is freed, a sample
of the requests answered in the window, drawn from the seed, is checked.
Each family's weights are made again from the seed on the device (the
same jitted generator the fleet was built from; nothing is taken from
the program), the sampled requests' token rows are gathered from them,
and the configuration's plain reference (``references/<name>.py``)
computes the logits in float64 on the host.

The number compared, ``logit_err``, is the largest over the sample of

    |served logit - reference logit| / sum_k |feat_k * head_k|,

a logit's error against the scale of the products that make it up, so
float rounding in the head reads the same for every logit.  The control
(``control.py``) is the reference's own arithmetic in float32 on the
chip at the next lower matmul precision than the configuration states.
"""
from __future__ import annotations

import importlib
from collections import defaultdict
from typing import Dict, Sequence

import numpy as np

import fleet


def reference_module(cfg: dict):
    return importlib.import_module("references." + cfg["reference"])


def by_family(cfg: dict, items):
    """``{family index: [(variant index, item), ...]}`` for items with a
    ``model`` name."""
    where = {name: (f, k) for f, names in enumerate(fleet.variant_names(cfg))
             for k, name in enumerate(names)}
    out = defaultdict(list)
    for model, item in items:
        f, k = where[model]
        out[f].append((k, item))
    return out


def rows_of(weights, k: int, docs: np.ndarray) -> np.ndarray:
    """Token rows ``[docs, tokens, d]`` of variant ``k`` (host float32)."""
    import jax.numpy as jnp
    return np.asarray(jnp.take(weights[k], jnp.asarray(docs), axis=0))


def reference_logits(cfg: dict, seed: int, requests: Sequence,
                     heads: Dict[str, np.ndarray]) -> Dict[int, tuple]:
    """``{request index: (logits, scale)}`` from the plain reference."""
    ref = reference_module(cfg)
    out = {}
    groups = by_family(cfg, [(r.model, r) for r in requests])
    for f, members in sorted(groups.items()):
        fam = fleet.make_family(cfg, seed, f)
        for k, r in members:
            out[r.index] = ref.logits(rows_of(fam.weights, k, r.docs),
                                      heads[r.model])
        del fam
    return out


def logit_err(served: np.ndarray, ref_logits: np.ndarray,
              scale: np.ndarray) -> float:
    served = np.asarray(served, np.float64)
    if served.shape != ref_logits.shape or not np.isfinite(served).all():
        return float("inf")
    return float((np.abs(served - ref_logits)
                  / np.maximum(scale, 1e-300)).max())


def compare(cfg: dict, seed: int, done: Sequence, heads) -> Dict[str, float]:
    """``logit_err`` over the sampled answered requests, and how many
    logits it covers."""
    refs = reference_logits(cfg, seed, [d.request for d in done], heads)
    worst, n = 0.0, 0
    for d in done:
        lg, scale = refs[d.request.index]
        worst = max(worst, logit_err(d.answer, lg, scale))
        n += lg.size
    return {"logit_err": worst, "logits": n}
