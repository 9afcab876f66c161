"""The control of ``correct`` fails the configured limit.

The control is the plain reference in the program's place, its head at
matmul precision ``high`` (three bfloat16 passes), one step below the
``highest`` the configurations state (``control.py``).  At a size a test
run holds, on three seeds, its ``logit_err`` has to read above each
configuration's limit, so that a change to that precision would fail.

    python -m pytest bench/tests/test_control.py
"""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import control  # noqa: E402

CELLS = [("ds7b-embed", "hot"), ("ds7b-embed", "paging"),
         ("mamba2-embed", "bulk")]


def _load(config, mix):
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", mix + ".json")) as f:
        mix = json.load(f)
    cfg["vocab_size"], cfg[cfg["width_key"]] = 1024, 512
    cfg["touched_row_blocks"] = 1
    return cfg, mix


@pytest.mark.parametrize("config,mix", CELLS)
@pytest.mark.parametrize("seed", [11, 2**33 + 12, 4_000_000_013])
def test_control_reads_above_the_limit(config, mix, seed):
    cfg, mix = _load(config, mix)
    r = control.readings(cfg, mix, seed, 16)
    assert r["control_high"] > cfg["checks"]["logit_err"]["limit"]


def test_three_pass_dot_is_between_bfloat16_and_float32():
    import ml_dtypes
    import numpy as np
    rng = np.random.default_rng(0)
    a = rng.standard_normal((8, 512)).astype(np.float32)
    b = rng.standard_normal((512, 4)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    three = control.dot_three_pass(a, b)
    bf16 = ml_dtypes.bfloat16
    one = (a.astype(bf16).astype(np.float64)
           @ b.astype(bf16).astype(np.float64))
    err3 = np.abs(three - exact).max()
    assert err3 < np.abs(one - exact).max() / 30
    assert err3 > np.abs(a @ b - exact).max()
