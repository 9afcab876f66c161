"""Crash-recovery benchmark -> BENCH_recovery.json.

Two halves of the DESIGN.md §11 durability story, measured:

  * **Journal replay cost** — a committed store is wrecked the way a
    crash mid-save wrecks it (k pending intents in the journal, k
    orphan pages no manifest references, k ``*.tmp`` staging files) and
    ``recover_backend`` is timed cleaning it up.  The recovery report's
    counts must equal the planted wreckage exactly — recovery that
    deletes the wrong number of things is worse than no recovery — and
    the clean-open cost (empty journal) is recorded as the floor every
    ordinary open pays.
  * **Warm restart under traffic** — the same open-loop request stream
    is served twice from one committed store: once to completion, and
    once killed after K dispatched batches (the frontend's snapshot is
    all that survives) then resumed on a FRESH engine whose pools
    rebuild lazily from the store.  Claims, all zero-tolerance on the
    virtual clock: the at-most-once ledger balances (served + shed ==
    offered, no id served twice), the union of pre- and post-restart
    logits is bit-exact against the uninterrupted run, at least one
    request was re-admitted (the restart did real work), and the
    resumed run's p99 stays within ``RESTART_P99_FACTOR`` of the
    uninterrupted p99.

Run standalone (``python -m benchmarks.bench_recovery [--smoke]``) or
through ``benchmarks.run``.  Always writes BENCH_recovery.json at the
repo root so CI tracks the recovery-cost trajectory PR over PR.
"""
from __future__ import annotations

import json
import os
import tempfile
import time
from typing import Dict, List

import numpy as np

from .common import Row, word2vec_scenario
from repro.core.store import ModelStore
from repro.serving.engine import (EmbeddingServingEngine, StorageModel,
                                  WeightServer)
from repro.serving.frontend import BatchComputeModel, ServingFrontend
from repro.serving.traffic import OpenLoopTraffic
from repro.storage.crashpoints import prime_store
from repro.storage.journal import Journal, recover_backend
from repro.storage.localdir import LocalDirBackend

JSON_PATH = os.path.join(os.path.dirname(__file__), "..",
                         "BENCH_recovery.json")

#: the resumed run replays the exact same virtual-clock history (queues,
#: EMA estimators and the clock itself are restored bit-for-bit), so its
#: p99 should EQUAL the uninterrupted run's; the factor is headroom for
#: a deliberate future change to resume ordering, not for noise
RESTART_P99_FACTOR = 1.5
SEED = 11
ZIPF = 1.1
#: deterministic virtual compute (same spelling as bench_traffic)
COMPUTE = BatchComputeModel(base=4e-4, per_request=4e-5)


# ------------------------------------------------ journal replay cost ----
def _wreck(path: str, k: int) -> None:
    """Strand the wreckage a crash mid-save leaves behind a committed
    store: ``k`` pending intents, ``k`` unreferenced pages, ``k`` temp
    staging files."""
    backend = LocalDirBackend(path)
    jr = Journal(backend)
    rng = np.random.default_rng(1000 + k)
    orphans: Dict[str, np.ndarray] = {}
    for i in range(k):
        jr.begin("save", keep=[])
        orphans[f"orphan{i:08d}"] = \
            rng.standard_normal((16, 16)).astype(np.float32)
    backend.put_pages(orphans)
    for i in range(k):
        with open(os.path.join(path, f"stray-{i:04d}.npy.tmp"), "w") as f:
            f.write("staging debris")
    backend.close()


def _recover_case(base: str, k: int, repeats: int = 3) -> Dict:
    """Best-of-N recovery timing at journal length ``k`` (every repeat
    wrecks a fresh copy of the primed store — recovery is destructive,
    so the wreckage cannot be reused)."""
    best = float("inf")
    counts_exact = True
    for rep in range(repeats):
        path = os.path.join(base, f"j{k}-r{rep}")
        prime_store(f"file://{path}")
        _wreck(path, k)
        backend = LocalDirBackend(path)
        t0 = time.perf_counter()
        report = recover_backend(backend)
        best = min(best, time.perf_counter() - t0)
        counts_exact = counts_exact and (
            report.recovered
            and report.pending_intents == k
            and report.orphan_pages_deleted == k
            and report.temp_files_swept == k)
        # recovery must converge: a second pass is a clean no-op
        counts_exact = counts_exact and not recover_backend(backend).recovered
        backend.close()
    # the floor every ordinary open pays: replaying a CLEAN journal
    clean_backend = LocalDirBackend(os.path.join(base, f"j{k}-r0"))
    t0 = time.perf_counter()
    for _ in range(8):
        recover_backend(clean_backend)
    clean_ms = (time.perf_counter() - t0) / 8 * 1e3
    clean_backend.close()
    return {"journal_len": k, "recover_ms": best * 1e3,
            "orphan_pages": k, "temp_files": k,
            "clean_open_ms": clean_ms, "counts_exact": counts_exact}


# ------------------------------------------------ warm restart -----------
def _payload_fn(task, docs_per_req):
    def payload(model, rid, rng):
        v = int(model.rsplit("-v", 1)[1])
        docs, _ = task.sample(docs_per_req, variant=v, seed=50_000 + rid)
        return docs
    return payload


def _restart_case(base: str, smoke: bool) -> Dict:
    scenario = dict(num_models=4, vocab=512, d=32,
                    block_shape=(32, 32), blocks_per_page=4)
    n_requests = 120 if smoke else 400
    kill_after = 5
    max_batch, docs_per_req = 4, 2
    rate, slo_s = 400.0, 0.2
    task, store, heads, _ = word2vec_scenario(**scenario)
    models = sorted(heads)
    url = f"file://{os.path.join(base, 'serving-store')}"
    store.save(url)
    cap = max(2, store.num_pages() // 2)

    def _gen():
        return OpenLoopTraffic(models, rate=rate, zipf_alpha=ZIPF,
                               slo_s=slo_s, seed=SEED,
                               payload_fn=_payload_fn(task, docs_per_req))

    def _engine():
        # a FRESH open every time: pools rebuild lazily from the store,
        # exactly what a restarted serving process does
        opened = ModelStore.open(url)
        server = WeightServer(opened, cap, "optimized_mru",
                              StorageModel("dram"))
        return EmbeddingServingEngine(server, heads, scheduler="fifo",
                                      overlap=True)

    # -- golden: the same stream served uninterrupted ----------------------
    fe0 = ServingFrontend(_engine(), max_batch=max_batch,
                          compute_model=COMPUTE, capture=True)
    st0 = fe0.run(_gen().generate(n_requests))
    golden = {rid: v.copy() for rid, v in fe0.results.items()}
    p99_golden = float(np.percentile(
        np.asarray(st0.request_latencies), 99)) * 1e3

    # -- interrupted: kill after K dispatches, resume from the snapshot ----
    snap_path = os.path.join(base, "fe-snapshot.json")
    fe1 = ServingFrontend(_engine(), max_batch=max_batch,
                          compute_model=COMPUTE, capture=True,
                          snapshot_path=snap_path)
    fe1.run(_gen().generate(n_requests), max_dispatches=kill_after)
    results_before = {rid: v.copy() for rid, v in fe1.results.items()}
    # simulated process death: only the snapshot file and the committed
    # store survive; engine, pools and the frontend object are gone
    with open(snap_path) as f:
        snap = json.load(f)
    t0 = time.perf_counter()
    fe2 = ServingFrontend.restore(_engine(), snap, _gen().generate(
        n_requests), compute_model=COMPUTE, capture=True,
        snapshot_path=snap_path)
    restore_ms = (time.perf_counter() - t0) * 1e3
    st2 = fe2.run(_gen().generate(n_requests))
    fe2.assert_ledger_conserved()
    p99_restart = float(np.percentile(
        np.asarray(st2.request_latencies), 99)) * 1e3

    dup_rids = set(results_before) & set(fe2.results)
    combined = dict(results_before)
    combined.update(fe2.results)
    logits_exact = (set(combined) == set(golden)
                    and all(np.array_equal(combined[rid], golden[rid])
                            for rid in golden))
    led = fe2.ledger
    ledger_conserved = (
        len(led.served) + len(led.shed) == len(led.offered)
        and not led.in_flight and fe2.pending_requests() == 0
        and len(led.offered) == n_requests)
    # the store a restarted process reopens must already be clean
    sb = LocalDirBackend(os.path.join(base, "serving-store"))
    store_clean = not sb.journal_records() and sb.sweep_temp() == 0
    sb.close()
    return {
        "requests": n_requests, "kill_after": kill_after,
        "max_batch": max_batch, "docs_per_req": docs_per_req,
        "rate_per_s": rate, "slo_ms": slo_s * 1e3,
        "scenario": scenario, "capacity_pages": cap,
        "served_before_kill": len(results_before),
        "readmitted": int(led.readmitted),
        "restore_ms": restore_ms,
        "p99_golden_ms": p99_golden,
        "p99_restart_ms": p99_restart,
        "duplicates": len(dup_rids),
        "logits_exact": bool(logits_exact),
        "ledger_conserved": bool(ledger_conserved),
        "store_clean": bool(store_clean),
    }


def run(smoke: bool = False) -> List[Row]:
    lens = (1, 8, 32) if smoke else (1, 8, 32, 256)
    rows: List[Row] = []
    configs = []
    with tempfile.TemporaryDirectory(prefix="bench-recovery-") as base:
        for k in lens:
            c = _recover_case(base, k)
            configs.append(c)
            rows.append((
                f"recovery/journal{k}",
                c["recover_ms"] * 1e3,             # us per recovery
                f"orphans={c['orphan_pages']};temps={c['temp_files']};"
                f"clean_open_ms={c['clean_open_ms']:.3f};"
                f"exact={int(c['counts_exact'])}"))
        restart = _restart_case(base, smoke)
    rows.append((
        "recovery/restart",
        restart["restore_ms"] * 1e3,               # us per restore
        f"readmitted={restart['readmitted']};"
        f"dups={restart['duplicates']};"
        f"exact={int(restart['logits_exact'])};"
        f"p99_ms={restart['p99_restart_ms']:.3f}"))

    payload = {
        "bench": "recovery",
        "scenario": {"journal_lens": list(lens),
                     "requests": restart["requests"],
                     "kill_after": restart["kill_after"],
                     "rate_per_s": restart["rate_per_s"],
                     "slo_ms": restart["slo_ms"],
                     "max_batch": restart["max_batch"],
                     "docs_per_req": restart["docs_per_req"],
                     "seed": SEED, "zipf": ZIPF, "smoke": smoke},
        "configs": configs,
        "restart": restart,
        # zero-tolerance internal claims (deterministic: virtual clock
        # latencies, content-addressed recovery, seeded streams)
        "recovery_counts_exact": all(c["counts_exact"] for c in configs),
        "restart_ledger_conserved": restart["ledger_conserved"],
        "restart_no_duplicates": restart["duplicates"] == 0,
        "restart_logits_exact": restart["logits_exact"],
        "restart_did_work": restart["readmitted"] > 0
                            and restart["served_before_kill"] > 0,
        "restart_p99_bounded":
            restart["p99_restart_ms"]
            <= RESTART_P99_FACTOR * restart["p99_golden_ms"],
        "restart_p99_factor_limit": RESTART_P99_FACTOR,
        "store_recovery_clean": restart["store_clean"],
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
    for claim in ("recovery_counts_exact", "restart_ledger_conserved",
                  "restart_no_duplicates", "restart_logits_exact",
                  "restart_did_work", "restart_p99_bounded",
                  "store_recovery_clean"):
        if not payload[claim]:
            print(f"# WARN recovery claim failed: {claim}")
    print(f"# wrote {os.path.abspath(JSON_PATH)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
