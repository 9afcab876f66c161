"""Open-loop traffic benchmark -> BENCH_traffic.json.

The paper's Fig.-8 claim under *load*: individual requests arriving
over (virtual) time, not pre-built batches.  For each offered-load rung
(a fraction of the measured naive service capacity µ) the same Poisson/
Zipf arrival stream is served twice through a memory-pressured server:

  * ``slo``   — the :class:`ServingFrontend`: continuous batch
    formation under the SLO, cost-based admission against the resident
    set, shedding of dead-on-arrival requests.
  * ``naive`` — per-arrival FIFO dispatch, one request per batch, no
    admission, no shedding: what a serving tier without a front end
    does.

Recorded per rung and policy: served-request latency p50/p99, goodput
(offered requests served within SLO), sheds, SLO misses.  The internal
claim — **SLO-aware formation + admission beats naive dispatch on p99
at the highest load rung** (where the naive queue grows without bound
while formation amortizes fetches and shedding keeps the served tail
inside the SLO) — is zero-tolerance in ``check_bench_regression.py``:
every quantity here lives on the virtual clock (deterministic fetch
seconds + a :class:`BatchComputeModel` for compute), so the whole JSON
is bit-stable under the fixed seed and there is no runner-noise excuse.

Run standalone (``python -m benchmarks.bench_traffic [--smoke]``) or
through ``benchmarks.run``.  Always writes BENCH_traffic.json at the
repo root so CI tracks the goodput/latency trajectory PR over PR.
"""
from __future__ import annotations

import contextlib
import json
import os
from typing import List

import numpy as np

from .common import Row, word2vec_scenario
from repro.serving.engine import (EmbeddingServingEngine, StorageModel,
                                  WeightServer)
from repro.serving.frontend import BatchComputeModel, ServingFrontend
from repro.serving.traffic import OpenLoopTraffic

JSON_PATH = os.path.join(os.path.dirname(__file__), "..",
                         "BENCH_traffic.json")
TRACE_PATH = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_traffic_trace.json")

#: offered load rungs as fractions of the measured naive capacity µ:
#: comfortably under, near saturation, and well past it
LOAD_FRACS = (0.5, 0.9, 2.0)
SEED = 11
ZIPF = 1.1
#: deterministic virtual compute: base + per-request seconds per batch
COMPUTE = BatchComputeModel(base=4e-4, per_request=4e-5)


def _payload_fn(task, docs_per_req):
    def payload(model, rid, rng):
        v = int(model.rsplit("-v", 1)[1])
        docs, _ = task.sample(docs_per_req, variant=v, seed=40_000 + rid)
        return docs
    return payload


def _engine(store, heads, cap):
    server = WeightServer(store, cap, "optimized_mru",
                          StorageModel("ssd"))
    return EmbeddingServingEngine(server, heads, scheduler="fifo",
                                  overlap=True)


def _serve(store, heads, cap, task, models, rate, slo_s, n_requests,
           policy, max_batch, docs_per_req, trace_path=None):
    """One policy pass over a freshly generated (identical: same seed)
    arrival stream against a fresh server; returns the metrics dict.
    ``trace_path``: record this pass with a clock-bound tracer and
    write the Chrome-trace there (the bench numbers are unchanged —
    tracing never touches the virtual clock's arithmetic)."""
    gen = OpenLoopTraffic(models, rate=rate, zipf_alpha=ZIPF,
                          slo_s=slo_s, seed=SEED,
                          payload_fn=_payload_fn(task, docs_per_req))
    engine = _engine(store, heads, cap)
    fe = ServingFrontend(engine, max_batch=max_batch, policy=policy,
                         compute_model=COMPUTE, capture=False)
    tracer = None
    activate = contextlib.nullcontext()
    if trace_path:
        from repro.obs import Tracer, use_tracer
        tracer = Tracer(clock=fe.clock)
        activate = use_tracer(tracer)
    with activate:
        st = fe.run(gen.generate(n_requests))
    # rung teardown: the channel ledger must account for every virtual
    # second this pass booked (frontend.run also asserts; cheap here)
    fe.clock.assert_conserved()
    if tracer is not None:
        from repro.obs import write_trace
        tracer.assert_matches_clock(fe.clock)
        write_trace(trace_path, tracer, clock=fe.clock)
    lat = np.asarray(st.request_latencies, dtype=np.float64)
    served = len(lat)
    return {
        "policy": policy,
        "offered": st.offered_requests,
        "served": served,
        "shed": st.shed_requests,
        "slo_misses": st.slo_misses,
        "goodput": st.goodput,
        "batches": st.batches,
        "p50_ms": float(np.percentile(lat, 50)) * 1e3 if served else None,
        "p99_ms": float(np.percentile(lat, 99)) * 1e3 if served else None,
        "queue_p50_ms": float(np.percentile(
            np.asarray(st.queue_latencies), 50)) * 1e3 if served else None,
        "hit_ratio": engine.server.pool.hit_ratio,
        "clock_ms": fe.clock.now * 1e3,
    }


def run(smoke: bool = False, trace: bool = False) -> List[Row]:
    if smoke:
        scenario = dict(num_models=4, vocab=512, d=32,
                        block_shape=(32, 32), blocks_per_page=4)
        n_requests, max_batch, docs_per_req = 150, 8, 2
    else:
        scenario = dict(num_models=6, vocab=1024, d=32,
                        block_shape=(32, 32), blocks_per_page=4)
        n_requests, max_batch, docs_per_req = 600, 8, 2
    task, store, heads, _ = word2vec_scenario(**scenario)
    models = sorted(heads)   # rank order for Zipf popularity
    cap = max(2, store.num_pages() // 2)   # memory-pressured pool

    # -- measure naive capacity µ (deterministic probe) ---------------------
    # a low-rate naive pass has no queueing, so its mean service time is
    # the per-request cost floor; µ = 1/s̄ is the saturation rate
    probe = _serve(store, heads, cap, task, models, rate=1.0, slo_s=10.0,
                   n_requests=40, policy="naive", max_batch=max_batch,
                   docs_per_req=docs_per_req)
    mean_service_s = probe["clock_ms"] * 1e-3 / probe["served"] \
        if probe["served"] else 1e-3
    # clock includes idle between sparse arrivals; use service latencies
    # instead: p50 of a queue-free run IS the service floor
    mean_service_s = probe["p50_ms"] * 1e-3
    mu = 1.0 / mean_service_s
    slo_s = max(0.005, 12.0 * mean_service_s)

    rows: List[Row] = []
    configs = []
    for frac in LOAD_FRACS:
        rate = frac * mu
        entry = {"load_frac": frac, "rate_per_s": rate}
        for policy in ("slo", "naive"):
            # --trace records the peak rung's slo pass (the run the
            # regression claims are about) without touching the numbers
            tp = TRACE_PATH if (trace and policy == "slo"
                                and frac == LOAD_FRACS[-1]) else None
            entry[policy] = _serve(store, heads, cap, task, models, rate,
                                   slo_s, n_requests, policy, max_batch,
                                   docs_per_req, trace_path=tp)
        configs.append(entry)
        s, n = entry["slo"], entry["naive"]
        rows.append((
            f"traffic/load{frac}",
            (s["p50_ms"] or 0.0) * 1e3,        # us per request (p50)
            f"p99_ms={s['p99_ms']:.3f};goodput={s['goodput']:.3f};"
            f"naive_p99_ms={n['p99_ms']:.3f};"
            f"naive_goodput={n['goodput']:.3f}"))

    peak = configs[-1]
    payload = {
        "bench": "traffic",
        "scenario": {**scenario, "requests": n_requests,
                     "max_batch": max_batch,
                     "docs_per_req": docs_per_req,
                     "capacity_pages": cap, "pages": store.num_pages(),
                     "zipf": ZIPF, "seed": SEED,
                     "load_fracs": list(LOAD_FRACS),
                     "slo_ms": slo_s * 1e3, "mu_per_s": mu,
                     "smoke": smoke},
        "configs": configs,
        # zero-tolerance internal claims (virtual clock: deterministic)
        "slo_beats_naive_p99_at_peak":
            peak["slo"]["p99_ms"] is not None
            and peak["naive"]["p99_ms"] is not None
            and peak["slo"]["p99_ms"] < peak["naive"]["p99_ms"],
        "slo_goodput_no_worse_at_peak":
            peak["slo"]["goodput"] >= peak["naive"]["goodput"],
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
    ap.add_argument("--trace", action="store_true",
                    help="record the peak-rung slo pass with a "
                         "clock-bound tracer and write "
                         "BENCH_traffic_trace.json (Chrome-trace form; "
                         "BENCH_traffic.json stays byte-identical)")
    args = ap.parse_args()
    rows = run(smoke=args.smoke, trace=args.trace)
    if args.trace:
        print(f"# wrote {os.path.abspath(TRACE_PATH)}")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    with open(JSON_PATH) as f:
        payload = json.load(f)
    if not payload["slo_beats_naive_p99_at_peak"]:
        print("# WARN SLO-aware formation did NOT beat naive dispatch "
              "on p99 at the highest load rung")
    if not payload["slo_goodput_no_worse_at_peak"]:
        print("# WARN SLO-aware goodput lost to naive dispatch at the "
              "highest load rung")
    print(f"# wrote {os.path.abspath(JSON_PATH)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
