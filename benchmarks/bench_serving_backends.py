"""Serving-backend benchmarks -> BENCH_serving.json + BENCH_storage.json
+ BENCH_sharding.json.

Axis 1 (compute): numpy vs device.  Serves the paper's multi-model
word2vec traffic twice per pool capacity — once with host
materialization (``backend="numpy"``) and once straight from the HBM
page slab through the dedup kernels (``backend="device"``) — and
records batches/sec plus per-batch latency percentiles.  Per-batch
latency is what the engine's stats record: virtual storage seconds for
the batch's page faults plus wall compute seconds.

The ``capacity_frac < 1`` rows are the fig-8 "working set exceeds the
pool" regime, where every batch faults pages; the paper's claim under
test is that executing against the deduplicated layout keeps the compute
path ahead of (or level with) host re-densification even there.

Axis 2 (storage): local dir vs SQLite vs simulated object store.  The
same traffic is served device-backend out of a store *reopened live*
from each ``repro.storage`` backend, with pool misses charged from that
backend's own ``microbench()``-calibrated StorageModel (the virtual
clock) and page faults issued as grouped ``get_pages`` batches.  The
claim under test: the grouped miss path amortizes the relational
backend's per-request overhead, so SQLite's p50 stays within 10% of the
``file://`` backend even in the all-miss fig-8 regime (``objsim`` shows
what a ~20 ms-seek remote tier does to the same traffic).  Written to
BENCH_storage.json.

Axis 3 (sharding): shard count x placement policy.  The same traffic is
served through a :class:`ShardedWeightServer` at 1/2/4 shards with the
per-shard slab capacity held FIXED below the total working set (one
accelerator's HBM doesn't grow when you add accelerators) — the
"working set exceeds one shard" regime.  Claims under test: adding a
second shard beats one thrashing slab on p50, and the sharer-weighted
placement's fetch-channel p50 (deterministic virtual clock: storage
misses + cross-shard borrow traffic) never loses to the hash-mod
baseline, because replicating the hot shared pages and homing each
model's singletons together keeps batches on-shard.  Written to
BENCH_sharding.json.

Run standalone (``python -m benchmarks.bench_serving_backends [--smoke]``)
or through ``benchmarks.run``.  Always writes the JSON files at the
repo root so CI tracks the perf trajectory PR over PR.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import time
from typing import List

import numpy as np

from .common import Row, word2vec_scenario
from repro.core.store import ModelStore
from repro.serving.engine import (EmbeddingServingEngine, ServeStats,
                                  StorageModel, WeightServer)
from repro.storage import (LocalDirBackend, ObjectStoreSimBackend,
                           SQLiteBackend)

JSON_PATH = os.path.join(os.path.dirname(__file__), "..",
                         "BENCH_serving.json")
STORAGE_JSON_PATH = os.path.join(os.path.dirname(__file__), "..",
                                 "BENCH_storage.json")
SHARDING_JSON_PATH = os.path.join(os.path.dirname(__file__), "..",
                                  "BENCH_sharding.json")
TRANSFER_JSON_PATH = os.path.join(os.path.dirname(__file__), "..",
                                  "BENCH_transfer.json")


def _traffic(task, num_models, batches, batch_size, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for b in range(batches):
        v = int(rng.integers(0, num_models))
        docs, _ = task.sample(batch_size, variant=v, seed=20_000 + b)
        out.append((f"w2v-v{v}", docs))
    return out


def _serve(store, heads, traffic, cap, backend, warmup=4, reps=3):
    """Serve the same traffic ``reps`` times on one warm engine and keep
    the best rep (the repo's ``timed()`` convention: OS noise on shared
    runners only ever adds time)."""
    server = WeightServer(store, cap, "optimized_mru", StorageModel("dram"),
                          backend=backend)
    engine = EmbeddingServingEngine(server, heads, scheduler="round_robin",
                                    overlap=False)

    for model, docs in traffic[:warmup]:   # jit warmup / pool warm
        engine.submit(model, docs)
    engine.run()

    best = None
    for rep in range(reps):
        engine.stats = ServeStats(overlapped=engine.overlap)
        server.pool.reset_stats()
        if backend == "device":
            loads0 = server.device_pool.loads
            evicts0 = server.device_pool.evicts
        for model, docs in traffic:        # same traffic every rep
            engine.submit(model, docs)
        t0 = time.perf_counter()
        stats = engine.run()
        wall = time.perf_counter() - t0
        lat = np.asarray(stats.latencies)
        out = {
            "batches_per_sec": stats.batches / max(wall, 1e-9),
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p99_ms": float(np.percentile(lat, 99)) * 1e3,
            "hit_ratio": server.pool.hit_ratio,
            "fetch_ms": stats.fetch_seconds * 1e3,
            "compute_ms": stats.compute_seconds * 1e3,
        }
        if backend == "device":
            out["device_batches"] = stats.device_batches
            out["dense_fallbacks"] = stats.dense_fallbacks
            out["slab_loads"] = server.device_pool.loads - loads0
            out["slab_evicts"] = server.device_pool.evicts - evicts0
        if best is None or out["p50_ms"] < best["p50_ms"]:
            best = out
    return best


def run_serving(smoke: bool = False) -> List[Row]:
    if smoke:
        scenario = dict(num_models=4, vocab=1024, d=64)
        batches, batch_size = 12, 64
        fracs = (0.5, 1.0)
    else:
        scenario = dict(num_models=6, vocab=4096, d=128)
        batches, batch_size = 30, 128
        fracs = (0.25, 0.5, 1.0)
    task, store, heads, _ = word2vec_scenario(**scenario)
    pages = store.num_pages()
    traffic = _traffic(task, scenario["num_models"], batches, batch_size)

    # Per-batch page working sets (what must co-reside in the slab for a
    # batch to serve off the device).  Capacities are floored just above
    # the worst batch: the fig-8 regime is TOTAL working set > pool >
    # one batch — every batch faults pages but never tears the slab.
    probe = WeightServer(store, 2)
    worst = max(len(probe.embedding_rows_pages(m, "embedding",
                                               np.unique(docs)))
                for m, docs in traffic)
    floor = worst + 1

    rows: List[Row] = []
    configs = []
    seen_caps = set()
    for frac in fracs:
        cap = min(pages, max(floor, int(pages * frac)))
        if cap in seen_caps:               # floor collapsed two fracs
            continue
        seen_caps.add(cap)
        res = {"capacity_frac": frac, "capacity_pages": cap,
               "worst_batch_pages": worst}
        for backend in ("numpy", "device"):
            res[backend] = _serve(store, heads, traffic, cap, backend)
        res["device_le_numpy_p50"] = \
            res["device"]["p50_ms"] <= res["numpy"]["p50_ms"]
        configs.append(res)
        for backend in ("numpy", "device"):
            r = res[backend]
            rows.append((
                f"serving_backends/pool{frac}/{backend}",
                r["p50_ms"] * 1e3,          # us per batch (p50)
                f"bps={r['batches_per_sec']:.1f};p99_ms={r['p99_ms']:.3f};"
                f"hit={r['hit_ratio']:.3f}"))

    payload = {
        "bench": "serving_backends",
        "scenario": {**scenario, "batches": batches,
                     "batch_size": batch_size, "pages": pages,
                     "storage": "dram", "smoke": smoke},
        "configs": configs,
    }
    with open(JSON_PATH, "w") as f:
        json.dump(payload, f, indent=2)
    return rows


# ------------------------------------------------------ storage-axis bench --
def _serve_from_backend(backend, heads, traffic, cap, storage,
                        warmup=4, reps=3):
    """Reopen the store live from ``backend`` and serve the traffic
    device-backend with the calibrated virtual clock; best-of-reps."""
    opened = ModelStore.open(backend)
    server = WeightServer(opened, cap, "optimized_mru", storage,
                          backend="device")
    engine = EmbeddingServingEngine(server, heads, scheduler="round_robin",
                                    overlap=True)
    for model, docs in traffic[:warmup]:
        engine.submit(model, docs)
    engine.run()

    best = None
    for _ in range(reps):
        engine.stats = ServeStats(overlapped=engine.overlap)
        engine.timeline.fetch_clock = engine.timeline.compute_clock = 0.0
        server.pool.reset_stats()
        for model, docs in traffic:
            engine.submit(model, docs)
        t0 = time.perf_counter()
        stats = engine.run()
        wall = time.perf_counter() - t0
        lat = np.asarray(stats.latencies)
        out = {
            "batches_per_sec": stats.batches / max(wall, 1e-9),
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p99_ms": float(np.percentile(lat, 99)) * 1e3,
            "hit_ratio": server.pool.hit_ratio,
            "fetch_ms": stats.fetch_seconds * 1e3,
            "compute_ms": stats.compute_seconds * 1e3,
            "device_batches": stats.device_batches,
            "dense_fallbacks": stats.dense_fallbacks,
        }
        if best is None or out["p50_ms"] < best["p50_ms"]:
            best = out
    return best


def run(smoke: bool = False) -> List[Row]:
    """All axes (what ``benchmarks.run`` invokes): compute backends ->
    BENCH_serving.json, storage backends -> BENCH_storage.json, shard
    count x placement -> BENCH_sharding.json, transfer path x miss rate
    -> BENCH_transfer.json."""
    return run_serving(smoke) + run_storage(smoke) + run_sharding(smoke) \
        + run_transfer(smoke)


# ----------------------------------------------------- transfer-axis bench --
def _transfer_scenario(num_models, vocab, d, seed=0,
                       block_shape=(32, 32), blocks_per_page=4):
    """N variants sharing one base embedding but each fine-tuning its
    OWN row stripe: any batch touches the shared pages plus exactly its
    model's private stripe, so per-batch cover ≈ half the union and the
    capacity ladder really sweeps the miss rate (batch ⊂ pool ⊂ union —
    the fig-8 regime).  The word2vec scenario can't produce this shape:
    its variants dedup so aggressively that every batch covers nearly
    the whole page universe."""
    from .common import store_config

    rng = np.random.default_rng(seed)
    base = (rng.standard_normal((vocab, d)) * 0.05).astype(np.float32)
    cfg = store_config(base, block_shape=block_shape,
                       blocks_per_page=blocks_per_page)
    store = ModelStore(cfg)
    heads = {}
    for v in range(num_models):
        emb = base.copy()
        lo, hi = v * vocab // num_models, (v + 1) * vocab // num_models
        emb[lo:hi] += (rng.standard_normal((hi - lo, d)) * 0.5
                       ).astype(np.float32)
        name = f"w2v-v{v}"
        store.register(name, {"embedding": emb})
        heads[name] = (rng.standard_normal((d, 16)) * 0.1
                       ).astype(np.float32)
    return store, heads


def _transfer_traffic(num_models, vocab, batches, batch_size,
                      seq=8, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for b in range(batches):
        v = int(rng.integers(0, num_models))
        docs = rng.integers(0, vocab, size=(batch_size, seq))
        out.append((f"w2v-v{v}", docs.astype(np.int64)))
    return out


def _serve_transfer(store, heads, traffic, cap, transfer, hbm,
                    warmup=4, reps=5, overlap=False):
    """One transfer-mode run with the host<->HBM channel ON the virtual
    clock (charge_transfer), calibrated once and shared across both
    modes so the only clock difference is per-page seeks vs one seek
    per group.  The headline (claim) runs are SERIAL — per-batch latency
    is the batch's own fetch+compute service time, the same no-queueing
    convention as the sharding axis (an overlapped timeline measures
    queue depth, which *rewards* a slower fetch channel).  ``overlap=
    True`` is the double-buffer demonstration run: fifo keeps the queue
    head predictable so prestaging engages, and overlap_fraction proves
    the next batch's transfer really rides under compute."""
    server = WeightServer(store, cap, "optimized_mru", StorageModel("dram"),
                          backend="device", transfer=transfer,
                          charge_transfer=True, hbm=hbm,
                          kernel_mode="xla")
    engine = EmbeddingServingEngine(server, heads, scheduler="fifo",
                                    overlap=overlap)
    for model, docs in traffic[:warmup]:
        engine.submit(model, docs)
    engine.run()

    # Percentiles POOL the reps instead of best-of: the pool trajectory
    # (and so the per-batch virtual clock) is deterministic and
    # identical between the two transfer modes, so pooled percentiles
    # compare PAIRED batches — best-of-rep would compare different reps.
    lats, flats = [], []
    best_bps = 0.0
    device_batches = fallbacks = 0
    agg = ServeStats()
    for _ in range(reps):
        engine.stats = ServeStats(overlapped=engine.overlap)
        engine.timeline.fetch_clock = engine.timeline.compute_clock = 0.0
        server.pool.reset_stats()
        for model, docs in traffic:
            engine.submit(model, docs)
        t0 = time.perf_counter()
        stats = engine.run()
        wall = time.perf_counter() - t0
        best_bps = max(best_bps, stats.batches / max(wall, 1e-9))
        lats.extend(stats.latencies)
        flats.extend(stats.fetch_latencies)
        agg.transfer_seconds += stats.transfer_seconds
        agg.transfer_pages += stats.transfer_pages
        agg.transfer_groups += stats.transfer_groups
        agg.transfer_bytes += stats.transfer_bytes
        agg.transfer_overlapped_bytes += stats.transfer_overlapped_bytes
        agg.group_sizes.extend(stats.group_sizes)
        device_batches += stats.device_batches
        fallbacks += stats.dense_fallbacks
    lat, flat = np.asarray(lats), np.asarray(flats)
    return {
        "batches_per_sec": best_bps,
        "p50_ms": float(np.percentile(lat, 50)) * 1e3,
        "p99_ms": float(np.percentile(lat, 99)) * 1e3,
        "fetch_p50_ms": float(np.percentile(flat, 50)) * 1e3,
        "fetch_p99_ms": float(np.percentile(flat, 99)) * 1e3,
        "miss_rate": 1.0 - server.pool.hit_ratio,
        "hit_ratio": server.pool.hit_ratio,
        "transfer_ms": agg.transfer_seconds * 1e3,
        "transfer_pages": agg.transfer_pages,
        "transfer_ops": agg.transfer_groups,
        "mean_group_size": agg.mean_group_size,
        "overlap_fraction": agg.overlap_fraction,
        "device_batches": device_batches,
        "dense_fallbacks": fallbacks,
    }


def run_transfer(smoke: bool = False) -> List[Row]:
    """per_page vs grouped host->HBM movement across a miss-rate ladder
    -> BENCH_transfer.json.

    Capacity fracs below 1.0 sweep the miss rate: the smaller the pool,
    the more pages every batch faults, and the more per-page seeks the
    grouped path's single seek amortizes away — so grouped p50 must win
    at every rung, with the gap *widening* as capacity shrinks (the
    fig-8 working-set-exceeds-pool regime)."""
    from repro.serving.device_pool import DevicePagePool

    if smoke:
        scenario = dict(num_models=4, vocab=2048, d=64)
        batches, batch_size = 14, 48
        fracs = (0.55, 0.7, 0.85)
    else:
        scenario = dict(num_models=4, vocab=4096, d=128)
        batches, batch_size = 24, 96
        fracs = (0.55, 0.7, 0.85)
    store, heads = _transfer_scenario(**scenario)
    pages = store.num_pages()
    traffic = _transfer_traffic(scenario["num_models"], scenario["vocab"],
                                batches, batch_size)

    probe = WeightServer(store, 2)
    worst = max(len(probe.embedding_rows_pages(m, "embedding",
                                               np.unique(docs)))
                for m, docs in traffic)
    floor = worst + 1

    # ONE measured host<->HBM channel, shared by both transfer modes: a
    # blocking bandwidth sweep over group sizes (bytes/s vs. n) fitted
    # to seconds = seek + bytes/bandwidth (serving/transfer.py).  xla
    # mode is the accelerator-shaped path off-TPU — a REAL device slab,
    # so a per-page miss really pays a device_put plus a slab-sized
    # functional update per page, which is exactly what grouping kills.
    cal_pool = DevicePagePool(store, max(floor, 8), kernel_mode="xla")
    hbm = cal_pool.transfer.storage_model()       # blocking measure() sweep
    del cal_pool

    rows: List[Row] = []
    configs = []
    seen_caps = set()
    for frac in fracs:
        cap = min(pages - 1, max(floor, int(pages * frac)))
        if cap in seen_caps:
            continue
        seen_caps.add(cap)
        entry = {"capacity_frac": frac, "capacity_pages": cap,
                 "worst_batch_pages": worst}
        for transfer in ("per_page", "grouped"):
            res = _serve_transfer(store, heads, traffic, cap, transfer, hbm)
            entry[transfer] = res
            rows.append((
                f"transfer/pool{frac}/{transfer}",
                res["p50_ms"] * 1e3,            # us per batch (p50)
                f"miss={res['miss_rate']:.3f};"
                f"group={res['mean_group_size']:.1f};"
                f"fetch_p50_ms={res['fetch_p50_ms']:.3f}"))
        # double-buffer demonstration: same grouped server driven by the
        # overlapped engine — prestaged bytes ride under compute
        entry["grouped_overlap"] = _serve_transfer(
            store, heads, traffic, cap, "grouped", hbm, overlap=True)
        entry["grouped_le_per_page_p50"] = \
            entry["grouped"]["p50_ms"] <= entry["per_page"]["p50_ms"] + 1e-9
        entry["grouped_le_per_page_fetch_p50"] = \
            entry["grouped"]["fetch_p50_ms"] \
            <= entry["per_page"]["fetch_p50_ms"] + 1e-9
        entry["fetch_gap_ms"] = entry["per_page"]["fetch_p50_ms"] \
            - entry["grouped"]["fetch_p50_ms"]
        entry["overlap_engaged"] = \
            entry["grouped_overlap"]["overlap_fraction"] > 0.0
        configs.append(entry)

    # fig-8 shape: the grouped win grows as capacity shrinks
    by_cap = sorted(configs, key=lambda e: e["capacity_pages"])
    gap_widens = by_cap[0]["fetch_gap_ms"] >= by_cap[-1]["fetch_gap_ms"] \
        - 1e-9 if len(by_cap) > 1 else True
    payload = {
        "bench": "transfer",
        "scenario": {**scenario, "batches": batches,
                     "batch_size": batch_size, "pages": pages,
                     "storage": "dram", "smoke": smoke},
        "hbm_channel": {"bandwidth_mbps": hbm.bw / 1e6,
                        "seek_us": hbm.seek * 1e6},
        "configs": configs,
        "grouped_le_per_page_p50_all": all(
            e["grouped_le_per_page_p50"] for e in configs),
        "grouped_le_per_page_fetch_p50_all": all(
            e["grouped_le_per_page_fetch_p50"] for e in configs),
        "gap_widens_as_capacity_shrinks": gap_widens,
        "overlap_engaged_all": all(e["overlap_engaged"] for e in configs),
    }
    with open(TRANSFER_JSON_PATH, "w") as f:
        json.dump(payload, f, indent=2)
    return rows


# ----------------------------------------------------- sharding-axis bench --
def _serve_sharded(store, heads, traffic, server_fn, warmup=4, reps=3):
    """Serial engine (per-batch latency = the batch's own fetch+compute
    service time, no queueing ambiguity) on a warm server; best-of-reps
    on wall p50.  The fetch-channel latencies are the virtual clock —
    deterministic, so placement policies compare noise-free."""
    server = server_fn()
    engine = EmbeddingServingEngine(server, heads, scheduler="round_robin",
                                    overlap=False)
    for model, docs in traffic[:warmup]:
        engine.submit(model, docs)
    engine.run()
    for model, docs in traffic:            # warm the steady-state residency
        engine.submit(model, docs)
    engine.run()

    best = None
    for _ in range(reps):
        engine.stats = ServeStats(overlapped=engine.overlap)
        server.pool.reset_stats()
        # server.stats accumulates across warmup+reps: report per-rep
        # deltas so the JSON's borrow numbers describe ONE traffic pass
        b_pages0 = server.stats.borrow_pages
        b_secs0 = server.stats.borrow_seconds
        shard0 = dict(server.stats.shard_batches)
        for model, docs in traffic:
            engine.submit(model, docs)
        t0 = time.perf_counter()
        stats = engine.run()
        wall = time.perf_counter() - t0
        lat = np.asarray(stats.latencies)
        flat = np.asarray(stats.fetch_latencies)
        out = {
            "batches_per_sec": stats.batches / max(wall, 1e-9),
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p99_ms": float(np.percentile(lat, 99)) * 1e3,
            "fetch_p50_ms": float(np.percentile(flat, 50)) * 1e3,
            "fetch_p99_ms": float(np.percentile(flat, 99)) * 1e3,
            "hit_ratio": server.pool.hit_ratio,
            "fetch_ms": stats.fetch_seconds * 1e3,
            "device_batches": stats.device_batches,
            "dense_fallbacks": stats.dense_fallbacks,
            "borrow_pages": server.stats.borrow_pages - b_pages0,
            "borrow_ms": (server.stats.borrow_seconds - b_secs0) * 1e3,
            "shard_batches": {
                str(k): v - shard0.get(k, 0) for k, v in sorted(
                    server.stats.shard_batches.items())},
        }
        if best is None or out["p50_ms"] < best["p50_ms"]:
            best = out
    return best


def run_sharding(smoke: bool = False) -> List[Row]:
    """shard count x placement -> BENCH_sharding.json."""
    from repro.serving.shard_pool import ShardedWeightServer

    if smoke:
        scenario = dict(num_models=4, vocab=2048, d=64)
        batches, batch_size = 16, 96
        shard_counts = (1, 2)
    else:
        scenario = dict(num_models=6, vocab=4096, d=128)
        batches, batch_size = 30, 128
        shard_counts = (1, 2, 4)
    task, store, heads, _ = word2vec_scenario(**scenario)
    pages = store.num_pages()
    traffic = _traffic(task, scenario["num_models"], batches, batch_size)

    probe = WeightServer(store, 2)
    worst = max(len(probe.embedding_rows_pages(m, "embedding",
                                               np.unique(docs)))
                for m, docs in traffic)
    # Fixed PER-SHARD capacity below the total working set: every batch
    # fits one shard's slab, the pool as a whole doesn't — one slab
    # churns (the fig-8 floor), a mesh partitions its way out.
    cap = min(pages - 1, max(worst + 1, int(pages * 0.8)))
    storage = StorageModel("hdd")        # miss cost dominates the clock

    rows: List[Row] = []
    configs = []
    for shards in shard_counts:
        entry = {"shards": shards, "capacity_per_shard": cap}
        for placement in ("hash", "sharers"):
            res = _serve_sharded(
                store, heads, traffic,
                lambda: ShardedWeightServer(
                    store, cap, "optimized_mru", storage,
                    shards=shards, placement=placement))
            entry[placement] = res
            rows.append((
                f"sharding/s{shards}/{placement}",
                res["p50_ms"] * 1e3,            # us per batch (p50)
                f"fetch_p50_ms={res['fetch_p50_ms']:.3f};"
                f"hit={res['hit_ratio']:.3f};"
                f"borrows={res['borrow_pages']}"))
        # placement claim on the deterministic fetch channel
        entry["sharers_le_hash_fetch_p50"] = \
            entry["sharers"]["fetch_p50_ms"] \
            <= entry["hash"]["fetch_p50_ms"] + 1e-9
        configs.append(entry)

    by_shards = {e["shards"]: e for e in configs}
    scaling_ok = by_shards[2]["sharers"]["p50_ms"] \
        <= by_shards[1]["sharers"]["p50_ms"]
    payload = {
        "bench": "sharding",
        "scenario": {**scenario, "batches": batches,
                     "batch_size": batch_size, "pages": pages,
                     "capacity_per_shard": cap,
                     "worst_batch_pages": worst,
                     "storage": "hdd", "smoke": smoke},
        "configs": configs,
        "sharers_le_hash_fetch_p50_all": all(
            e["sharers_le_hash_fetch_p50"] for e in configs
            if e["shards"] > 1),
        "two_shard_p50_le_one_shard": scaling_ok,
    }
    with open(SHARDING_JSON_PATH, "w") as f:
        json.dump(payload, f, indent=2)
    return rows


def run_storage(smoke: bool = False) -> List[Row]:
    """local vs sqlite vs objsim serving -> BENCH_storage.json."""
    if smoke:
        scenario = dict(num_models=4, vocab=1024, d=64)
        batches, batch_size = 12, 64
    else:
        scenario = dict(num_models=6, vocab=2048, d=64)
        batches, batch_size = 24, 96
    task, store, heads, _ = word2vec_scenario(**scenario)
    pages = store.num_pages()
    traffic = _traffic(task, scenario["num_models"], batches, batch_size)
    bh, bw = store.cfg.dedup.block_shape
    page_bytes = store.cfg.blocks_per_page * bh * bw \
        * store.native_page_dtype().itemsize

    probe = WeightServer(store, 2)
    worst = max(len(probe.embedding_rows_pages(m, "embedding",
                                               np.unique(docs)))
                for m, docs in traffic)
    # the fig-8 all-miss regime: one batch fits, the working set doesn't
    cap = min(pages, worst + 1)

    tmp = tempfile.mkdtemp(prefix="bench_storage_")
    rows: List[Row] = []
    results = {}
    try:
        backends = [
            ("file", LocalDirBackend(os.path.join(tmp, "file_store"))),
            ("sqlite", SQLiteBackend(os.path.join(tmp, "models.db"))),
            ("objsim", ObjectStoreSimBackend()),  # ~20 ms seek, 200 MB/s
        ]
        for name, backend in backends:
            store.save(backend)
            prof = backend.microbench(page_bytes=page_bytes)
            storage = StorageModel(kind=f"calibrated:{name}",
                                   bandwidth=prof.bandwidth, seek=prof.seek)
            res = _serve_from_backend(backend, heads, traffic, cap, storage)
            res["profile"] = {"bandwidth_mbps": prof.bandwidth / 1e6,
                              "seek_us": prof.seek * 1e6,
                              "page_bytes": page_bytes}
            if name == "objsim":
                res["backend_get_calls"] = backend.get_calls
                res["backend_pages_fetched"] = backend.pages_fetched
            results[name] = res
            rows.append((
                f"storage_backends/{name}/device",
                res["p50_ms"] * 1e3,            # us per batch (p50)
                f"bps={res['batches_per_sec']:.1f};"
                f"p99_ms={res['p99_ms']:.3f};hit={res['hit_ratio']:.3f};"
                f"bw={prof.bandwidth/1e6:.0f}MB/s"))
            backend.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    sqlite_ok = results["sqlite"]["p50_ms"] \
        <= 1.10 * results["file"]["p50_ms"]
    payload = {
        "bench": "storage_backends",
        "scenario": {**scenario, "batches": batches,
                     "batch_size": batch_size, "pages": pages,
                     "capacity_pages": cap, "worst_batch_pages": worst,
                     "page_bytes": page_bytes, "smoke": smoke},
        "backends": results,
        "sqlite_within_10pct_of_file_p50": sqlite_ok,
    }
    with open(STORAGE_JSON_PATH, "w") as f:
        json.dump(payload, f, indent=2)
    return rows


def main() -> int:
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small fast configuration for CI")
    args = ap.parse_args()
    rows = run(smoke=args.smoke)
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    with open(JSON_PATH) as f:
        payload = json.load(f)
    bad = [c for c in payload["configs"]
           if c["capacity_frac"] < 1.0 and not c["device_le_numpy_p50"]]
    for c in bad:
        print(f"# WARN device p50 {c['device']['p50_ms']:.3f}ms > numpy "
              f"{c['numpy']['p50_ms']:.3f}ms at frac={c['capacity_frac']}")
    with open(STORAGE_JSON_PATH) as f:
        spayload = json.load(f)
    if not spayload["sqlite_within_10pct_of_file_p50"]:
        print(f"# WARN sqlite p50 "
              f"{spayload['backends']['sqlite']['p50_ms']:.3f}ms > 1.1x "
              f"file p50 {spayload['backends']['file']['p50_ms']:.3f}ms")
    with open(SHARDING_JSON_PATH) as f:
        shpayload = json.load(f)
    if not shpayload["sharers_le_hash_fetch_p50_all"]:
        print("# WARN sharers placement lost the fetch-channel p50 to "
              "hash-mod at some shard count")
    if not shpayload["two_shard_p50_le_one_shard"]:
        print("# WARN 2-shard p50 did not beat the 1-shard thrash floor")
    with open(TRANSFER_JSON_PATH) as f:
        tpayload = json.load(f)
    if not tpayload["grouped_le_per_page_p50_all"]:
        print("# WARN grouped transfer lost the p50 to per_page at some "
              "miss rate")
    if not tpayload["gap_widens_as_capacity_shrinks"]:
        print("# WARN grouped-vs-per_page fetch gap did not widen as "
              "capacity shrank")
    print(f"# wrote {os.path.abspath(JSON_PATH)}")
    print(f"# wrote {os.path.abspath(STORAGE_JSON_PATH)}")
    print(f"# wrote {os.path.abspath(SHARDING_JSON_PATH)}")
    print(f"# wrote {os.path.abspath(TRANSFER_JSON_PATH)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
