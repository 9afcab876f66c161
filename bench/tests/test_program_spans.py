"""The program's own spans in a traced run, and the six readers of them.

Each run case drives the whole harness (``run.run``) with ``--trace 1``
on the CPU at a small size, past its look for a chip, with the slab's
gathers on XLA (the CPU's host mirror has no device result to wait on),
then checks that every new per-layer metric of the cell reads a positive
number from the ``repro.*`` spans or the pool's counters they carry; the
harness installs no tracer, so the program's spans are there because the
profiler recorded.

    python -m pytest bench/tests/test_program_spans.py
"""
from __future__ import annotations

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import program_spans  # noqa: E402
import run  # noqa: E402

READERS = {
    "ds7b-embed.hot": ("page_map_ms_per_req", "schedule_ms_per_req",
                       "gather_prep_ms_per_req", "device_wait_ms_per_req"),
    "ds7b-embed.paging": ("evict_ms_per_req", "victim_scan_per_evict"),
}


@pytest.fixture
def small(monkeypatch):
    """The cells at a size a test run holds, gathers on XLA."""
    from repro.serving.device_pool import DevicePagePool
    load = run.load_cell

    def load_small(name):
        spec, cell, cfg, mix = load(name)
        cfg["vocab_size"], cfg[cfg["width_key"]] = 1024, 512
        cfg["touched_row_blocks"] = 1
        mix["pregenerate"] = min(mix["pregenerate"], 256)
        return spec, cell, cfg, mix

    monkeypatch.setattr(run, "load_cell", load_small)
    monkeypatch.setattr(DevicePagePool, "mode", lambda self: "xla")


@pytest.mark.parametrize("cell", sorted(READERS))
def test_readers_positive_where_the_work_happened(small, cell, capsys):
    out = run.run(["--workload", cell, "--seed", "2147483659",
                   "--seconds", "1", "--trace", "1"], require_tpu=False)
    assert out["correct"] is True
    for name in READERS[cell]:
        assert out["metrics"][name]["value"] > 0, name
    # the other cells' new metrics are not this cell's
    for other, names in READERS.items():
        if other != cell:
            assert not set(names) & set(out["metrics"])
    # the bench wrappers and the program spans at the same boundaries,
    # and the idle time by program span, as the command line prints them
    program_spans.main([os.path.join(program_spans.TRACE_ROOT, cell),
                        "--requests", str(out["attempted"])])
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    agree = report["agreement_ms_per_req"]
    for wrapper in ("bench.submit", "bench.engine.run", "bench.fetch",
                    "bench.gather"):
        bench_ms, program_ms = agree[wrapper]
        assert 0 < program_ms <= bench_ms, wrapper
    idle = dict(report["idle_by_program_span"])
    assert "repro.page_map" in idle
    assert not any(n.startswith("bench.") for n in idle)


def test_load_reads_only_program_spans(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation
    program_spans._CACHE.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.window"):
            with TraceAnnotation("repro.run", seq=4):
                with TraceAnnotation("bench.head"):
                    with TraceAnnotation("repro.head", seq=4):
                        pass
    finally:
        jax.profiler.stop_trace()
    spans = program_spans.load(str(tmp_path))
    assert [n for _, _, n in spans] == ["repro.run", "repro.head"]
    (r0, r1, _), (h0, h1, _) = spans
    assert r0 <= h0 <= h1 <= r1


def test_names_lose_annotation_arguments():
    assert program_spans._name("repro.evict#seq=3,model=a#") == "repro.evict"
    assert program_spans._name("repro.evict") == "repro.evict"


def test_readers_return_none_without_program_spans(monkeypatch):
    """A program that opens no ``repro.*`` span, as before the host
    clock, leaves every new metric out without raising; a reader reads
    only its own span."""
    data = types.SimpleNamespace(requests=10, lo=0.0, hi=1.0,
                                 cell={"name": "no-such-cell"})
    for names in READERS.values():
        for name in names:
            assert run.metric_reader(name).read(data) is None, name
    monkeypatch.setattr(program_spans, "trace_dir", lambda r: "trace")
    monkeypatch.setattr(program_spans, "_read", lambda d: (
        [(0.0, 0.5, "repro.page_map")], {"repro.evict": []}))
    assert run.metric_reader("evict_ms_per_req").read(data) is None
    assert run.metric_reader("victim_scan_per_evict").read(data) is None
    assert run.metric_reader("page_map_ms_per_req").read(data) \
        == pytest.approx(50.0)


def test_victim_scan_reads_the_counters_evictions_carry(monkeypatch):
    """The counters' change between the window's first and last
    ``repro.evict``; evictions outside the window do not count."""
    marks = [(0.05, {"victim_scanned": 10, "evictions": 1}),
             (0.1, {"victim_scanned": 100, "evictions": 5}),
             (0.4, {"victim_scanned": 250, "evictions": 6}),
             (0.5, {"victim_scanned": 400, "evictions": 8}),
             (2.0, {"victim_scanned": 9000, "evictions": 9})]
    monkeypatch.setattr(program_spans, "trace_dir", lambda r: "trace")
    monkeypatch.setattr(program_spans, "_read",
                        lambda d: ([], {"repro.evict": marks}))
    data = types.SimpleNamespace(requests=10, lo=0.08, hi=1.0,
                                 cell={"name": "ds7b-embed.paging"})
    assert run.metric_reader("victim_scan_per_evict").read(data) \
        == pytest.approx(100.0)
    data.lo, data.hi = 0.3, 0.45               # one eviction: no change
    assert run.metric_reader("victim_scan_per_evict").read(data) is None
