"""Fault-injection serving benchmark -> BENCH_faults.json.

Serves identical multi-model embedding traffic out of one committed
store at increasing storage fault rates (0 / 5% / 10%: transient read
errors, bit-flip corruption, lock contention, latency spikes) through
the recovery layer (``storage/faults.py`` + the ModelStore retry /
verify / quarantine path, DESIGN.md §8) and records:

  * **bit-exactness** — the logits of every faulted run must equal the
    rate-0 run bit for bit.  Recovery is invisible to the math or it
    is not recovery.
  * **bounded tails** — per-batch latency p50/p99 per rate (virtual
    fetch seconds + wall compute; retry backoff and injected latency
    ride the clock's own ``fault`` channel).  The p99 at 10% faults
    must stay within a constant factor of the fault-free p99 — chaos
    costs backoff, never a cliff.
  * **recovery accounting** — retries / corrupt pages detected /
    quarantine re-fetches / virtual backoff seconds per rate.
  * **the naive path dies** — the same 10%-fault traffic served with
    the recovery layer disabled (zero retries, no verification) either
    crashes or silently serves corrupt logits; the benchmark records
    which, proving the layer is load-bearing.

Run standalone (``python -m benchmarks.bench_faults [--smoke]``) or
through ``benchmarks.run``.  Always writes BENCH_faults.json at the
repo root so CI tracks the chaos trajectory PR over PR.
"""
from __future__ import annotations

import json
import os
import time
from typing import List, Optional

import numpy as np

from .common import Row, word2vec_scenario
from repro.core.store import ModelStore
from repro.serving.engine import (EmbeddingServingEngine, StorageModel,
                                  WeightServer)
from repro.storage import MemoryBackend
from repro.storage.faults import (FaultInjectingBackend, FaultSpec,
                                  RetryPolicy, StorageFaultError)

JSON_PATH = os.path.join(os.path.dirname(__file__), "..",
                         "BENCH_faults.json")

#: chaos tail tolerance.  A tail batch legitimately absorbs a few
#: injected latency spikes (FaultSpec.latency_ms each) plus bounded
#: retry backoff — the claim under test is the absence of an UNBOUNDED
#: retry storm, so the bound is a factor over the fault-free p99 plus
#: an absolute grace of a handful of spikes.  A convergence bug (retry
#: loop thrashing, quarantine never draining) blows through this by
#: orders of magnitude.
P99_FACTOR = 3.0
P99_SPIKE_BUDGET = 4          # spikes the worst batch may absorb


def _traffic(task, num_models, batches, batch_size, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for b in range(batches):
        v = int(rng.integers(0, num_models))
        docs, _ = task.sample(batch_size, variant=v, seed=30_000 + b)
        out.append((f"w2v-v{v}", docs))
    return out


def _spec(rate: float, seed: int = 11) -> FaultSpec:
    """All fault kinds at ``rate`` (latency spikes at 2x: they are the
    cheap kind), one seed so every rate is its own deterministic run."""
    return FaultSpec(transient=rate, corrupt=rate, lock=rate,
                     torn=rate, latency=min(1.0, 2 * rate), seed=seed)


def _serve_chaos(inner: MemoryBackend, heads, traffic, cap: int,
                 rate: float, recover: bool = True):
    """One full traffic pass against a freshly wrapped backend; returns
    (per-run dict, stacked logits).  ``recover=False`` is the naive
    path: zero retries, verification forced off."""
    backend = FaultInjectingBackend(inner, _spec(rate)) if rate > 0 \
        else inner
    opened = ModelStore.open(backend)
    if not recover:
        opened.retry_policy = RetryPolicy(max_retries=0)
        opened.verify_pages = False
    server = WeightServer(opened, cap, "optimized_mru",
                          StorageModel("dram"), backend="device")
    engine = EmbeddingServingEngine(server, heads, scheduler="fifo",
                                    overlap=True)
    # No warmup pass: the host tier caches every page it has faulted, so
    # recovery only happens on FIRST touch — a warmup would absorb the
    # entire fault schedule outside the measured window.  Every rate
    # serves the identical cold-start traffic instead, so the runs stay
    # paired and the measured tail includes real recovery work.
    logits: List[np.ndarray] = []
    t0 = time.perf_counter()
    for model, docs in traffic:
        engine.submit(model, docs)
        engine.run(max_batches=1)          # one batch -> capture logits
        logits.append(np.asarray(engine.last_logits, np.float32))
    wall = time.perf_counter() - t0
    stats, fs = engine.stats, server.stats
    lat = np.asarray(stats.latencies)
    out = {
        "rate": rate,
        "batches": stats.batches,
        "batches_per_sec": stats.batches / max(wall, 1e-9),
        "p50_ms": float(np.percentile(lat, 50)) * 1e3,
        "p99_ms": float(np.percentile(lat, 99)) * 1e3,
        "hit_ratio": server.pool.hit_ratio,
        "retries": fs.retries,
        "corrupt_detected": fs.corrupt_detected,
        "refetch_pages": fs.refetch_pages,
        "degraded_batches": stats.degraded_batches,
        "fault_backoff_ms": fs.fault_backoff_seconds * 1e3,
        "injected": dict(getattr(backend, "injected", {})),
    }
    return out, np.concatenate([l.reshape(-1) for l in logits])


def run(smoke: bool = False) -> List[Row]:
    if smoke:
        scenario = dict(num_models=4, vocab=1024, d=64)
        batches, batch_size = 12, 64
        rates = (0.0, 0.05, 0.10)
    else:
        scenario = dict(num_models=6, vocab=2048, d=64)
        batches, batch_size = 24, 96
        rates = (0.0, 0.02, 0.05, 0.10)
    task, store, heads, _ = word2vec_scenario(**scenario)
    pages = store.num_pages()
    traffic = _traffic(task, scenario["num_models"], batches, batch_size)

    probe = WeightServer(store, 2)
    worst = max(len(probe.embedding_rows_pages(m, "embedding",
                                               np.unique(docs)))
                for m, docs in traffic)
    # the all-miss fig-8 regime: every batch faults pages, so every
    # batch actually exercises the injected backend
    cap = min(pages, worst + 1)

    inner = MemoryBackend()
    store.save(inner)

    rows: List[Row] = []
    configs = []
    baseline: Optional[np.ndarray] = None
    for rate in rates:
        res, logits = _serve_chaos(inner, heads, traffic, cap, rate)
        if baseline is None:
            baseline = logits
            res["logits_exact"] = True
        else:
            res["logits_exact"] = bool(np.array_equal(baseline, logits))
        configs.append(res)
        rows.append((
            f"faults/rate{rate}",
            res["p50_ms"] * 1e3,               # us per batch (p50)
            f"p99_ms={res['p99_ms']:.3f};retries={res['retries']};"
            f"corrupt={res['corrupt_detected']};"
            f"exact={int(res['logits_exact'])}"))

    # -- the naive path dies ------------------------------------------------
    worst_rate = rates[-1]
    naive = {"rate": worst_rate, "recovery": False}
    try:
        res, logits = _serve_chaos(inner, heads, traffic, cap, worst_rate,
                                   recover=False)
        naive["crashed"] = False
        naive["logits_exact"] = bool(np.array_equal(baseline, logits))
        naive["corrupt_detected"] = res["corrupt_detected"]
    except (StorageFaultError, KeyError) as exc:
        naive["crashed"] = True
        naive["error"] = type(exc).__name__
        naive["logits_exact"] = False
    # either failure mode proves the recovery layer is load-bearing
    naive["dies"] = naive["crashed"] or not naive["logits_exact"]

    p99_0 = configs[0]["p99_ms"]
    p99_worst = configs[-1]["p99_ms"]
    grace_ms = P99_SPIKE_BUDGET * _spec(0.10).latency_ms
    payload = {
        "bench": "faults",
        "scenario": {**scenario, "batches": batches,
                     "batch_size": batch_size, "pages": pages,
                     "capacity_pages": cap, "worst_batch_pages": worst,
                     "spec": str(_spec(0.10)), "smoke": smoke},
        "configs": configs,
        "naive": naive,
        "logits_exact_all": all(c["logits_exact"] for c in configs),
        "p99_bounded": p99_worst <= P99_FACTOR * p99_0 + grace_ms,
        "p99_factor_limit": P99_FACTOR,
        "p99_grace_ms": grace_ms,
        "naive_path_dies": naive["dies"],
    }
    with open(JSON_PATH, "w") as f:
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
    if not payload["logits_exact_all"]:
        print("# WARN recovered serving was NOT bit-exact under faults")
    if not payload["p99_bounded"]:
        print(f"# WARN p99 under {payload['configs'][-1]['rate']:.0%} "
              f"faults exceeded {P99_FACTOR}x the fault-free p99")
    if not payload["naive_path_dies"]:
        print("# WARN the naive (no-recovery) path survived bit-exact — "
              "the fault schedule is too soft to prove anything")
    print(f"# wrote {os.path.abspath(JSON_PATH)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
