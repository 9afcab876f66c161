"""Observability stack tests (obs/ + the instrumented request path):
tracer semantics, the EXACT per-channel conservation invariant through
real frontend runs (1 and 2 shards), request-span stage identities,
ring retention, exporters + trace_report, the metrics registry, and
the zero-perturbation guarantee — logits and bench-style JSON are
bit-identical with tracing on vs off.
"""
import dataclasses
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.bufferpool import BufferPool, PoolConfig
from repro.launch.serve import REPORT_FIELDS, _build_registry, build_store
from repro.obs import (NULL_TRACER, HostClock, MetricsRegistry, Tracer,
                       get_tracer, to_chrome_trace, use_tracer,
                       validate_chrome_trace, write_trace)
from repro.obs.export import load_trace
from repro.data.pipeline import SyntheticTextTask
from repro.serving import (BatchComputeModel, EmbeddingServingEngine,
                           OpenLoopTraffic, ServeStats, ServingFrontend,
                           ShardedWeightServer, StorageModel,
                           VirtualClock, WeightServer)
from repro.storage.faults import RecoveryStats

ROOT = Path(__file__).resolve().parent.parent


def _scenario(vocab=512, d=32, num_models=3, block=(32, 32), l=4, seed=0):
    task = SyntheticTextTask(vocab=vocab, d=d, seed=seed)
    store, heads = build_store(task, num_models=num_models,
                               block_shape=block, blocks_per_page=l)
    return task, store, heads


def _doc_payload(task, docs_per_req=3, seed_base=700):
    def payload(model, rid, rng):
        v = int(model.rsplit("-v", 1)[1])
        docs, _ = task.sample(docs_per_req, variant=v,
                              seed=seed_base + rid)
        return docs
    return payload


def _frontend(task, store, heads, shards=1):
    if shards == 1:
        server = WeightServer(store, max(2, store.num_pages() // 2),
                              storage=StorageModel("dram"))
    else:
        server = ShardedWeightServer(store,
                                     max(4, store.num_pages() - 2),
                                     storage=StorageModel("dram"),
                                     shards=shards, placement="sharers")
    engine = EmbeddingServingEngine(server, heads, scheduler="fifo")
    return ServingFrontend(engine, max_batch=4,
                           compute_model=BatchComputeModel())


def _traced_run(shards=1, n=40, rate=400.0, tracer=None):
    task, store, heads = _scenario(num_models=3)
    fe = _frontend(task, store, heads, shards=shards)
    gen = OpenLoopTraffic([f"word2vec-v{v}" for v in range(3)],
                          rate=rate, zipf_alpha=1.1, slo_s=0.5, seed=5,
                          payload_fn=_doc_payload(task))
    if tracer is None:
        tracer = Tracer(clock=fe.clock)
    with use_tracer(tracer):
        st = fe.run(gen.generate(n))
    return fe, st, tracer


# ------------------------------------------------------------ tracer core --
def test_null_tracer_is_default_and_allocates_nothing():
    tr = get_tracer()
    assert tr is NULL_TRACER and tr.enabled is False
    h1, h2 = tr.span("a"), tr.span("b", kind="x", pages=3)
    assert h1 is h2                            # one shared handle
    with h1 as sp:
        assert sp.set(bytes=1) is sp           # shared inert span
    assert tr.spans() == [] and tr.emit("r", 0.0, 1.0) is None


def test_span_channel_and_charge_must_travel_together():
    tr = Tracer()
    with pytest.raises(ValueError):
        tr.span("fetch", channel="storage")
    with pytest.raises(ValueError):
        tr.span("fetch", charge=0.1)


def test_span_nesting_parents_and_out_of_order_close():
    tr = Tracer()
    with tr.span("outer") as o:
        with tr.span("inner") as i:
            assert i.parent == o.sid
        assert tr.open_spans() == [o]
    a, b = tr.spans()
    assert (a.name, b.name) == ("inner", "outer")   # close order
    sp = tr.span_begin("x")
    tr.span_begin("y")
    with pytest.raises(RuntimeError, match="out of order"):
        tr.span_end(sp)


def test_use_tracer_scopes_and_restores():
    tr = Tracer()
    assert get_tracer() is NULL_TRACER
    with use_tracer(tr):
        assert get_tracer() is tr
    assert get_tracer() is NULL_TRACER


def test_charged_spans_replay_clock_accumulation_exactly():
    clk = VirtualClock()
    tr = Tracer(clock=clk)
    rng = np.random.default_rng(0)
    for _ in range(200):                 # awkward floats on purpose
        d = float(rng.random()) * 1e-3
        ch = ("storage", "compute")[int(rng.integers(2))]
        with tr.span("w", channel=ch, charge=d):
            clk.advance(d, ch)
    tr.assert_matches_clock()            # exact ==, no tolerance
    clk.advance(1e-7, "storage")         # an advance outside any span
    with pytest.raises(AssertionError, match="escaped its span"):
        tr.assert_matches_clock()


def test_assert_matches_clock_rejects_open_spans():
    clk = VirtualClock()
    tr = Tracer(clock=clk)
    tr.span_begin("left-open")
    with pytest.raises(AssertionError, match="open spans"):
        tr.assert_matches_clock()


def test_ring_retention_drops_oldest_without_breaking_anything():
    clk = VirtualClock()
    tr = Tracer(clock=clk, ring=4)
    with tr.span("outer") as outer:          # stays OPEN while ring churns
        for i in range(10):
            with tr.span(f"s{i}", channel="c", charge=0.5):
                clk.advance(0.5, "c")
    assert tr.dropped == 7                   # 11 finished - 4 retained
    kept = tr.spans()
    assert len(kept) == 4 and kept[-1] is outer
    assert [s.name for s in kept] == ["s7", "s8", "s9", "outer"]
    assert all(s.parent == outer.sid for s in kept[:-1])  # tree intact
    tr.assert_matches_clock()                # conservation survives drops
    with pytest.raises(ValueError):
        Tracer(ring=0)


def test_virtual_clock_assert_conserved_detects_leak():
    clk = VirtualClock(start=2.0)
    clk.advance(0.25, "storage")
    clk.tick_to(3.0)
    clk.assert_conserved()
    clk.now += 0.5                           # a second conjured channel-free
    with pytest.raises(AssertionError, match="leaked"):
        clk.assert_conserved()


# ----------------------------------------- conservation through the stack --
@pytest.mark.parametrize("shards", [1, 2])
def test_frontend_run_span_channels_equal_clock_exactly(shards):
    fe, st, tracer = _traced_run(shards=shards)
    assert len(st.request_latencies) > 0
    assert tracer.dropped == 0
    # every channel the clock booked, matched exactly — including idle
    assert set(fe.clock.channels) == set(tracer.channel_seconds)
    for ch in fe.clock.channels:
        assert tracer.channel_seconds[ch] == fe.clock.spent(ch)
    assert fe.clock.spent("idle") > 0.0 and fe.clock.spent("compute") > 0.0
    tracer.assert_matches_clock(fe.clock)
    fe.clock.assert_conserved()


def test_request_spans_carry_exact_stage_identities():
    fe, st, tracer = _traced_run()
    reqs = tracer.find(kind="request")
    served = [sp for sp in reqs if not sp.attrs["shed"]]
    assert len(served) == len(st.request_latencies)
    for sp in served:
        at = sp.attrs
        assert at["queue_s"] + at["service_s"] == at["latency_s"]
        assert at["fetch_s"] + at["compute_s"] == at["service_s"]
        assert sp.end_t - sp.start_t == pytest.approx(at["latency_s"])
    # trace-derived latency per rid == the stats' ledger
    assert sorted(sp.attrs["latency_s"] for sp in served) \
        == sorted(st.request_latencies)
    # span trees from deeper layers arrived too
    assert tracer.find(name="dispatch", kind="frontend")
    assert tracer.find(name="fetch", kind="engine")
    assert tracer.find(name="schedule", kind="policy")


# ---------------------------------------------------- zero perturbation --
def _bench_style_metrics(fe, st):
    """The BENCH_traffic per-pass dict shape (subset, same keys)."""
    lat = np.asarray(st.request_latencies, dtype=np.float64)
    return {
        "offered": st.offered_requests, "served": len(lat),
        "shed": st.shed_requests, "slo_misses": st.slo_misses,
        "goodput": st.goodput, "batches": st.batches,
        "p50_ms": float(np.percentile(lat, 50)) * 1e3,
        "p99_ms": float(np.percentile(lat, 99)) * 1e3,
        "hit_ratio": fe.engine.server.pool.hit_ratio,
        "clock_ms": fe.clock.now * 1e3,
    }


def test_tracing_on_vs_off_is_bit_identical():
    fe_on, st_on, _ = _traced_run()
    fe_off, st_off, _ = _traced_run(tracer=NULL_TRACER)
    # bench-style JSON: byte-identical
    assert json.dumps(_bench_style_metrics(fe_on, st_on), sort_keys=True) \
        == json.dumps(_bench_style_metrics(fe_off, st_off), sort_keys=True)
    # per-request logits: bit-identical
    assert fe_on.results.keys() == fe_off.results.keys()
    for rid in fe_on.results:
        np.testing.assert_array_equal(fe_on.results[rid],
                                      fe_off.results[rid])
    # and the virtual clocks agree to the last ulp
    assert fe_on.clock.now == fe_off.clock.now
    assert fe_on.clock.channels == fe_off.clock.channels


# ------------------------------------------------------------ host clock --
def test_host_tracer_nests_carries_seq_and_reads_perf_counter(monkeypatch):
    ticks = iter(float(t) for t in range(100, 200))
    monkeypatch.setattr("repro.obs.trace.time.perf_counter",
                        lambda: next(ticks))
    tr = Tracer(clock=HostClock())
    with tr.span("run", kind="engine") as run:
        run.set(seq=7)
        with tr.span("fetch", kind="engine") as fetch:
            with tr.span("evict", kind="pool") as ev:
                pass
        with tr.span("other", seq=9) as other:
            pass
    assert (fetch.parent, ev.parent) == (run.sid, fetch.sid)
    assert fetch.attrs["seq"] == ev.attrs["seq"] == 7   # inherited
    assert other.attrs["seq"] == 9                      # its own wins
    # one perf_counter read at each open and each close, in order
    assert (run.start_t, fetch.start_t, ev.start_t) == (100.0, 101.0, 102.0)
    assert (ev.end_t, fetch.end_t, run.end_t) == (103.0, 104.0, 107.0)
    assert [s.name for s in tr.spans()] == ["evict", "fetch", "other", "run"]


def test_host_tracer_spans_land_in_profiler_trace(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData
    tr = Tracer(clock=HostClock())
    jax.profiler.start_trace(str(tmp_path))
    try:
        with use_tracer(tr):
            with get_tracer().span("outer", kind="engine", seq=3,
                                   model="m", rows=[1, 2]) as sp:
                sp.set(pages=5)
                with get_tracer().span("inner", kind="kernel"):
                    pass
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {}
    for plane in ProfileData.from_file(path[0]).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("repro."):
                        events[e.name] = (e.start_ns, e.end_ns,
                                          dict(e.stats))
    assert set(events) == {"repro.outer", "repro.inner"}
    # scalar attrs ride along (rows, a list, does not); set() reaches it
    assert events["repro.outer"][2] == {"seq": 3, "model": "m", "pages": 5}
    assert events["repro.inner"][2] == {"seq": 3}
    o0, o1, _ = events["repro.outer"]
    i0, i1, _ = events["repro.inner"]
    assert o0 <= i0 <= i1 <= o1


def test_host_tracer_rejects_charge():
    tr = Tracer(clock=HostClock())
    with pytest.raises(ValueError, match="host-clock"):
        tr.span("fetch", channel="storage", charge=0.1)
    with tr.span("fetch", kind="engine"):       # attributed spans are fine
        pass
    assert tr.open_spans() == [] and len(tr.spans()) == 1


def _device_engine(tracer, kernel_mode):
    task, store, heads = _scenario(vocab=1024, l=2)     # 18 pages
    server = WeightServer(store, 16,
                          storage=StorageModel("dram"), backend="device",
                          kernel_mode=kernel_mode)
    engine = EmbeddingServingEngine(server, heads, scheduler="round_robin",
                                    overlap=True)
    logits = []
    with use_tracer(tracer):
        for b in range(6):
            v = b % 3
            docs, _ = task.sample(8, variant=v, seed=300 + b)
            engine.submit(f"word2vec-v{v}", docs)
            engine.run(max_batches=1)
            logits.append(engine.last_logits.copy())
    return engine, logits


@pytest.mark.parametrize("kernel_mode", ["host", "xla"])
def test_host_tracing_on_vs_off_is_bit_identical(kernel_mode):
    tracer = Tracer(clock=HostClock())
    on, logits_on = _device_engine(tracer, kernel_mode)
    off, logits_off = _device_engine(NULL_TRACER, kernel_mode)
    for a, b in zip(logits_on, logits_off):
        np.testing.assert_array_equal(a, b)
    pool_on, pool_off = on.server.pool, off.server.pool
    assert (pool_on.hits, pool_on.misses, pool_on.evictions,
            pool_on.victim_scanned) == (pool_off.hits, pool_off.misses,
                                        pool_off.evictions,
                                        pool_off.victim_scanned)
    assert on.stats.device_batches == off.stats.device_batches == 6
    # every span of a request carries its batch's seq
    names = {sp.name for sp in tracer.spans()}
    assert {"submit", "page_map", "run", "schedule", "fetch",
            "fault_group", "pool_group", "compute", "gather",
            "gather_prep", "kernel"} <= names
    if kernel_mode == "xla":
        assert {"upload", "to_host", "head"} <= names
    assert pool_on.evictions and tracer.find(name="evict", kind="pool")
    assert tracer.find(name="schedule", kind="policy")
    for sp in tracer.spans():
        assert isinstance(sp.attrs.get("seq"), int), sp.name
    runs = tracer.find(name="run")
    assert sorted(sp.attrs["seq"] for sp in runs) == list(range(6))


def test_get_tracer_follows_the_profiler(tmp_path):
    import glob
    import threading

    import jax
    from jax.profiler import ProfileData
    assert get_tracer() is NULL_TRACER            # no profile, no tracer
    installed = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr = get_tracer()
        assert tr is not NULL_TRACER and isinstance(tr.clock, HostClock)
        assert get_tracer() is tr                 # one per thread
        other = []
        t = threading.Thread(target=lambda: other.append(get_tracer()))
        t.start()
        t.join()
        assert other[0] is not tr and isinstance(other[0].clock, HostClock)
        with use_tracer(installed):               # an installed one wins
            assert get_tracer() is installed
        with get_tracer().span("run", kind="engine", seq=2):
            with get_tracer().span("evict", kind="pool"):
                pass
        assert tr.open_spans() == []
    finally:
        jax.profiler.stop_trace()
    assert get_tracer() is NULL_TRACER
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name: dict(e.stats)
             for plane in ProfileData.from_file(path[0]).planes
             if plane.name.startswith("/host:")
             for ln in plane.lines for e in ln.events
             if e.name.startswith("repro.")}
    assert names == {"repro.run": {"seq": 2}, "repro.evict": {"seq": 2}}


def test_evict_span_carries_the_pool_counters():
    pool = BufferPool(PoolConfig(2, "lru"))
    tr = Tracer(clock=HostClock())
    with use_tracer(tr):
        for p in (0, 1, 2, 3):
            pool.access("m", p)
    marks = [(sp.attrs["victim_scanned"], sp.attrs["evictions"])
             for sp in tr.find(name="evict", kind="pool")]
    assert marks == [(2, 1), (4, 2)]
    assert marks[-1] == (pool.victim_scanned, pool.evictions)
    off = BufferPool(PoolConfig(2, "lru"))        # tracing off: same counts
    for p in (0, 1, 2, 3):
        off.access("m", p)
    assert (off.victim_scanned, off.evictions) == marks[-1]


# ----------------------------------------------------------- pool counter --
@pytest.mark.parametrize("policy,first,second", [
    ("lru", 4, 4),                 # the resident set
    ("lfu", 8, 8),                 # the resident set, the evictable list
    ("optimized_mru", 4 + 2, 2),   # the index's build, one page a set
    ("locality_lru", 4 + 2, 2),
])
def test_victim_scanned_counts_every_pass(policy, first, second):
    # 4 resident pages in 2 locality sets, nothing pinned
    pool = BufferPool(PoolConfig(4, policy),
                      page_locality={p: p % 2 for p in range(10)})
    for p in range(4):
        pool.access("m", p)
    assert pool.victim_scanned == 0            # no eviction yet
    pool.access("m", 4)
    assert pool.evictions == 1
    assert pool.victim_scanned == first
    pool.access("m", 5)
    assert pool.victim_scanned == first + second
    pool.reset_stats()
    assert pool.victim_scanned == 0


def test_pool_register_into_exposes_victim_scanned():
    pool = BufferPool(PoolConfig(2, "lru"))
    reg = MetricsRegistry()
    pool.register_into(reg, "pool")
    assert reg.names("counter") == ["pool.hits", "pool.misses",
                                    "pool.evictions", "pool.victim_scanned"]
    before = reg.snapshot()
    for p in (0, 1, 0, 2):
        pool.access("m", p)
    assert reg.diff(before) == {"pool.hits": 1.0, "pool.misses": 3.0,
                                "pool.evictions": 1.0,
                                "pool.victim_scanned": 2.0}
    # launch/serve.py's registry carries the pool's counters too
    task, store, heads = _scenario(num_models=2)
    server = WeightServer(store, 4, storage=StorageModel("dram"))
    engine = EmbeddingServingEngine(server, heads)
    serve_reg = _build_registry(engine.stats, server, engine, None)
    assert "pool.victim_scanned" in serve_reg
    assert serve_reg.kind("pool.victim_scanned") == "counter"


# -------------------------------------------------------------- exporters --
def test_chrome_trace_export_validates_and_roundtrips(tmp_path):
    fe, st, tracer = _traced_run()
    doc = to_chrome_trace(tracer, clock=fe.clock)
    assert validate_chrome_trace(doc) == []
    # conservation re-checkable from the document alone, still exact
    other = doc["otherData"]
    assert other["tracer_channel_seconds"] == other["clock_channels"]
    # one track per channel + the requests track
    names = {ev["args"]["name"] for ev in doc["traceEvents"]
             if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert {"channel/storage", "channel/compute", "channel/idle",
            "requests"} <= names

    cj = write_trace(str(tmp_path / "t.json"), tracer, clock=fe.clock)
    jl = write_trace(str(tmp_path / "t.jsonl"), tracer)
    from_chrome, from_jsonl = load_trace(cj), load_trace(jl)
    assert len(from_chrome) == len(from_jsonl) == len(tracer.spans())
    # request-span stage attrs survive the JSON roundtrip bit-exactly
    for spans in (from_chrome, from_jsonl):
        served = [s for s in spans if s["kind"] == "request"
                  and not s["attrs"]["shed"]]
        assert served
        for s in served:
            at = s["attrs"]
            assert at["queue_s"] + at["service_s"] == at["latency_s"]


def test_trace_report_script_passes_and_fails(tmp_path):
    fe, st, tracer = _traced_run()
    path = write_trace(str(tmp_path / "t.json"), tracer, clock=fe.clock)
    script = str(ROOT / "scripts" / "trace_report.py")
    ok = subprocess.run([sys.executable, script, path],
                        capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "exact identities OK" in ok.stdout
    assert "critical path" in ok.stdout
    # corrupt one stage attr -> the exact check must hard-fail
    doc = json.loads(Path(path).read_text())
    for ev in doc["traceEvents"]:
        if ev.get("cat") == "request" and not ev["args"].get("shed"):
            ev["args"]["queue_s"] += 1e-9
            break
    bad_path = tmp_path / "bad.json"
    bad_path.write_text(json.dumps(doc))
    bad = subprocess.run([sys.executable, script, str(bad_path)],
                         capture_output=True, text=True)
    assert bad.returncode == 1
    assert "queue_s+service_s != latency_s" in bad.stderr


# -------------------------------------------------------- metrics registry --
def test_metrics_registry_kinds_snapshot_and_diff():
    reg = MetricsRegistry()
    box = {"n": 0, "vals": [1.0, 2.0, 3.0], "by": {"a": 1.0}}
    reg.counter("x.n", lambda: box["n"])
    reg.histogram("x.vals", lambda: box["vals"])
    reg.gauge("x.by", lambda: box["by"])
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("x.n", lambda: 0)
    with pytest.raises(ValueError, match="unknown metric kind"):
        reg.register("x.y", "meter", lambda: 0)
    before = reg.snapshot()
    assert before["x.vals"] == {"count": 3, "mean": 2.0,
                                "p50": 2.0, "p99": 3.0}
    box["n"] = 7
    box["vals"].append(9.0)
    d = reg.diff(before)
    assert d == {"x.n": 7.0}               # counters only, by delta
    assert "x.n" in reg and len(reg) == 3
    assert reg.names("histogram") == ["x.vals"]


def test_serve_and_recovery_stats_register_every_field():
    reg = MetricsRegistry()
    st, rs = ServeStats(), RecoveryStats()
    st.register_into(reg)
    rs.register_into(reg)
    for f in dataclasses.fields(ServeStats):
        assert f"serve.{f.name}" in reg
    for f in dataclasses.fields(RecoveryStats):
        assert f"recovery.{f.name}" in reg
    # kinds follow the field shapes
    assert reg.kind("serve.latencies") == "histogram"
    assert reg.kind("serve.shard_batches") == "gauge"
    assert reg.kind("serve.requests") == "counter"
    st.requests = 3
    assert reg.snapshot()["serve.requests"] == 3.0


# ------------------------------------------------------- report-line audit --
def test_every_serve_stat_has_exactly_one_report_line():
    """REPORT_FIELDS is the audit: every ServeStats field maps to
    exactly one [tag] line (dict => at most one; this pins at least
    one, and that the line actually prints the mapped key)."""
    fields = {f.name for f in dataclasses.fields(ServeStats)}
    assert set(REPORT_FIELDS) == fields
    known_tags = {"serve", "device", "transfer", "prefetch", "shards",
                  "faults", "traffic"}
    src = (ROOT / "src/repro/launch/serve.py").read_text()
    for field, (tag, key) in REPORT_FIELDS.items():
        assert tag in known_tags, field
        assert f"[{tag}]" in src, f"{field}: no [{tag}] line"
        for k in key.split("/"):
            assert k in src, f"{field}: key {k!r} not printed"
