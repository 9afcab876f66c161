#!/usr/bin/env python3
"""The control of ``correct``: the reference at the next lower precision.

The configurations state float32 weights and a float32 head at matmul
precision ``highest``.  The next step down, the one a later change might
take, is ``high``: three bfloat16 passes (``a_hi b_hi + a_hi b_lo +
a_lo b_hi``, float32 accumulation).  The control puts the plain
reference in the program's place, computes each request's pooled
features in float32 on the device and its head at ``high`` (on a TPU,
``jnp.dot(..., precision="high")``; elsewhere the same arithmetic on the
host, :func:`dot_three_pass`), and reads ``logit_err`` against the
float64 reference on the same requests.
It has to read above the configuration's limit.  Beside it, the same
arithmetic at ``highest`` (one float32 pass) is read as a witness of
what float32 alone gives.

    python3 bench/control.py --config ds7b-embed --traffic hot \\
        --seeds 11 12 13 --requests 64

The benchmark's own runs never run this.  It needs no chip to run, but
its readings count only from a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


def dot_three_pass(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """``a @ b`` in the arithmetic of precision ``high``, on the host:
    each float32 operand split into a bfloat16 high half and a bfloat16
    low half, the three products ``a_hi b_hi + a_hi b_lo + a_lo b_hi``
    (each exact in float32) accumulated in float32.  The CPU ignores
    matmul precision, so this is the control's arithmetic where no TPU
    computes it."""
    import ml_dtypes
    bf16 = ml_dtypes.bfloat16

    def split(x):
        x = np.asarray(x, np.float32)
        hi = x.astype(bf16).astype(np.float32)
        return hi, (x - hi).astype(bf16).astype(np.float32)

    a_hi, a_lo = split(a)
    b_hi, b_lo = split(b)
    return a_hi @ b_hi + (a_hi @ b_lo + a_lo @ b_hi)


def readings(cfg: dict, mix: dict, seed: int, n_requests: int) -> dict:
    """``logit_err`` of the control (``high``) and of float32 at
    ``highest``, over the first ``n_requests`` of the mix's window
    stream under ``seed``."""
    import jax
    import jax.numpy as jnp
    import check
    import fleet
    import traffic

    ref = check.reference_module(cfg)
    fams = [fleet.make_family(cfg, seed, f)
            for f in range(len(cfg["families"]))]
    tcs = {}
    heads = {}
    for fam in fams:
        fh = np.asarray(fam.heads)
        for k, name in enumerate(fam.variants):
            tcs[name] = fam.token_class
            heads[name] = fh[k]
    reqs = traffic.make_requests(mix, cfg, tcs, seed, n_requests)
    pooled = jax.jit(lambda w, d: w[d].mean(axis=1))
    highest = jax.jit(lambda x, h: jnp.dot(x, h, precision="highest"))
    if jax.default_backend() == "tpu":
        control = jax.jit(lambda x, h: jnp.dot(x, h, precision="high"))
    else:
        def control(x, h):
            return dot_three_pass(np.asarray(x), np.asarray(h))
    worst = {"control_high": 0.0, "float32_highest": 0.0}
    groups = check.by_family(cfg, [(r.model, r) for r in reqs])
    for f, members in sorted(groups.items()):
        fam = fams[f]
        for k, r in members:
            rows = check.rows_of(fam.weights, k, r.docs)
            lg, scale = ref.logits(rows, heads[r.model])
            feats = pooled(fam.weights[k], jnp.asarray(r.docs))
            head = jnp.asarray(heads[r.model])
            for key, fn in (("control_high", control),
                            ("float32_highest", highest)):
                got = np.asarray(fn(feats, head))
                worst[key] = max(worst[key], check.logit_err(got, lg, scale))
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int, default=64)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    with open(os.path.join(BENCH, "configs", args.config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", args.traffic + ".json")) as f:
        mix = json.load(f)
    import jax
    dev = jax.devices()[0]
    limit = cfg["checks"]["logit_err"]["limit"]
    for seed in args.seeds:
        r = readings(cfg, mix, seed, args.requests)
        print(json.dumps({"config": args.config, "traffic": args.traffic,
                          "seed": seed, "device": dev.device_kind,
                          "limit": limit, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
