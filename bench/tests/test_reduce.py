"""The trace reduction, on hand-made intervals and on a recorded trace.

``fixtures/hot_trace.json`` is 0.3 s of a traced ``ds7b-embed.hot``
window on one TPU v5e (device ops and the benchmark's host spans, as
``reduce.load`` read them).  Each reduction is checked against a
brute-force count on a 1 us grid.

    python -m pytest bench/tests/test_reduce.py
"""
from __future__ import annotations

import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import reduce  # noqa: E402
from metrics import dedup_embedding_roofline  # noqa: E402
from metrics import transfer_ms_per_req  # noqa: E402

DEV = [(1.0, 2.0, "m:a"), (1.5, 3.0, "m:b"), (5.0, 6.0, "n:c")]
SPANS = [(0.0, 10.0, "bench.window"), (0.5, 4.5, "bench.fetch"),
         (1.0, 1.2, "bench.storage_fetch"), (4.0, 4.4, "bench.stage"),
         (6.5, 7.0, "bench.head")]


def test_busy_and_idle_union():
    assert reduce.busy_seconds(DEV, 0, 10) == pytest.approx(3.0)
    assert reduce.idle_gaps(DEV, 0, 10) == [(0, 1.0), (3.0, 5.0), (6.0, 10)]
    # clipped to the window
    assert reduce.busy_seconds(DEV, 1.75, 5.5) == pytest.approx(1.75)


def test_gaps_named_by_innermost_span():
    gaps = reduce.gaps_by_span(DEV, SPANS, 0, 10)
    assert gaps == pytest.approx({"window": 4.5, "fetch": 1.6, "stage": 0.4,
                                  "head": 0.5})
    assert sum(gaps.values()) == pytest.approx(7.0)


def test_self_time():
    got = reduce.self_seconds(SPANS, "bench.fetch",
                              ("bench.storage_fetch", "bench.stage"), 0, 10)
    assert got == pytest.approx(4.0 - 0.2 - 0.4)


def test_self_time_of_several_names():
    # a stage that reads storage, a commit with a per-page load nested in
    # it: nested transfer spans count once, the storage read not at all
    spans = [(0.0, 10.0, "bench.window"),
             (1.0, 2.0, "bench.stage"), (1.2, 1.5, "bench.storage_fetch"),
             (3.0, 4.0, "bench.transfer_commit"),
             (3.5, 3.7, "bench.page_load"),
             (3.6, 3.65, "bench.storage_fetch"),
             (5.0, 5.5, "bench.storage_fetch")]
    names = ("bench.stage", "bench.transfer_commit", "bench.page_load")
    got = reduce.self_seconds(spans, names, ("bench.storage_fetch",), 0, 10)
    assert got == pytest.approx(1.0 - 0.3 + 1.0 - 0.05)
    run = types.SimpleNamespace(trace=reduce.Trace([], spans), requests=4,
                                lo=0.0, hi=10.0)
    assert transfer_ms_per_req.read(run) == pytest.approx(1.65e3 / 4)


def test_op_and_module_seconds():
    assert reduce.op_seconds(DEV, 0, 10) == pytest.approx(
        {"m:a": 1.0, "m:b": 1.5, "n:c": 1.0})
    assert reduce.module_seconds(DEV, "m", 0, 10) == pytest.approx(2.5)


def test_op_name_from_hlo_text():
    hlo = ("%copy.9 = f32[256,64,2,64]{3,1,2,0} copy(f32[256,64,2,64] "
           "%bitcast.7)")
    assert reduce.op_name(hlo) == "copy"
    assert reduce.op_name("%dedup_embedding.1 = f32[8] custom-call()") \
        == "dedup_embedding"


def test_roofline_bytes_from_the_algorithm():
    # 256 ids of a 4096-wide float32 row: read once, written once
    assert dedup_embedding_roofline.gather_bytes(256, 4096) \
        == 2 * 256 * 4096 * 4


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "fixtures", "hot_trace.json")) as f:
        return reduce.Trace.from_json(json.load(f))


def _grid(lo, hi, step=1e-6):
    return np.arange(lo, hi, step) + step / 2


def test_recorded_busy_matches_grid(recorded):
    lo, hi = reduce.window_of(recorded.spans)
    t = _grid(lo, hi)
    busy = np.zeros(len(t), bool)
    for s, e, _ in recorded.device:
        busy |= (t >= s) & (t < e)
    got = reduce.busy_seconds(recorded.device, lo, hi)
    assert got == pytest.approx(busy.sum() * 1e-6, abs=2e-5)
    assert 0 < got < hi - lo


def test_recorded_gaps_cover_idle_time(recorded):
    lo, hi = reduce.window_of(recorded.spans)
    gaps = reduce.gaps_by_span(recorded.device, recorded.spans, lo, hi)
    idle = (hi - lo) - reduce.busy_seconds(recorded.device, lo, hi)
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-9)
    assert set(gaps) <= {"window", "submit", "engine.run", "fetch",
                         "gather", "head", "stage", "prestage",
                         "unannotated"}
    # brute force: idle grid points inside a fetch span with no gather,
    # stage or other span nested deeper
    t = _grid(lo, hi)
    busy = np.zeros(len(t), bool)
    for s, e, _ in recorded.device:
        busy |= (t >= s) & (t < e)
    label = np.full(len(t), "", object)
    depth = np.full(len(t), -1.0)
    for s, e, n in recorded.spans:
        m = (t >= s) & (t < e) & (s >= depth)
        label[m], depth[m] = n, s
    fetch_idle = ((label == "bench.fetch") & ~busy).sum() * 1e-6
    assert gaps.get("fetch", 0.0) == pytest.approx(fetch_idle, abs=5e-5)


def test_recorded_self_time(recorded):
    lo, hi = reduce.window_of(recorded.spans)
    kids = ("bench.storage_fetch", "bench.stage", "bench.transfer_commit")
    got = reduce.self_seconds(recorded.spans, "bench.fetch", kids, lo, hi)
    total = reduce.span_seconds(recorded.spans, "bench.fetch", lo, hi)
    assert 0 < got <= total
