#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload ds7b-embed.hot --seed 7 \\
        --seconds 30 --trace 0

Everything is found by name.  The cell (``workloads``) names a
configuration (``configs`` -> its file) and a traffic mix
(``bench/traffic/<traffic>.json``); the configuration names its kind
(``"kind"``, default ``embedding``), the module ``bench/kinds/<kind>.py``
that builds, serves and checks that kind of model (:func:`kind_module`;
the contract is in ``bench/kinds/__init__.py``); each metric the cell
reports is read by ``bench/metrics/<metric>.py`` (:func:`metric_reader`).
Adding a cell, a mix, a metric, or a configuration of a new kind (its
kind module, reference, mixes and metrics) adds files and entries and
edits none.

A run, in one process on the chips it finds:

  set-up   the kind makes the fleet's weights from the seed, registers
           every variant through ``DedupDB`` (Alg. 1) and commits the
           store to ``sqlite:///<checkout>/.scratch/bench/<cell>.db``,
           reopens it with ``DedupDB.open``, builds the engine on it, makes
           the requests and serves the warm-up ones; then the gathers from
           staged page stacks are compiled, ``gc.collect()`` and
           ``gc.freeze()``;
  window   closed-loop callers for ``--seconds`` (``loop.py``); with
           ``--trace 1`` under the profiler, with the kind's host spans
           around the program's layers;
  check    the sampled answers against the plain reference (the kind's
           ``check``), after the program's state is freed.

The last line of standard output is the result: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device`` and, traced,
``breakdown``; last comes ``checks``, each compared number beside its
limit, which are also the last lines on standard error.  A run is
correct when the sampled answers match the reference, no batch was
answered off the device path (the kind's ``failed_batches``), and
nothing compiled in the window.
Without a TPU, or with fewer chips than the cell asks for, the run exits
2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse        # noqa: E402
import gc              # noqa: E402
import importlib       # noqa: E402
import importlib.util  # noqa: E402
import json            # noqa: E402
import os              # noqa: E402
import shutil          # noqa: E402
import sqlite3         # noqa: E402
import sys             # noqa: E402
from collections import Counter  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SCRATCH = os.path.join(ROOT, ".scratch", "bench")
sys.path.insert(0, BENCH)


def info(msg: str) -> None:
    print(msg, flush=True)


class Refused(Exception):
    """The run cannot start here (no chip, unknown cell)."""


def load_cell(name: str):
    """``(spec, cell, cfg, mix)`` of the named cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    return spec, cell, cfg, mix


def tpu_devices(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX's platform is "
                      f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < count:
        raise Refused(f"needs {count} TPU chips; JAX sees {len(devs)}")
    return devs


class CompileEvents:
    """Counts new executables (lowerings to MLIR), backend compiles and
    persistent-cache hits, and sums compile seconds."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax
        self.lowered = self.compiled = self.cache_hits = 0
        self.seconds = 0.0
        self.names = []                  # programs lowered, in order
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, fun_name=None, **_):
        if event == self.LOWER:
            self.lowered += 1
            self.names.append(fun_name)
        elif event == self.COMPILE:
            self.compiled += 1
        elif event == self.CACHE_HIT:
            self.cache_hits += 1
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration

    def snapshot(self):
        return (self.lowered, self.compiled, self.cache_hits)


def setup_jax(cfg: dict) -> str:
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_default_matmul_precision",
                      cfg["matmul_precision"])
    return cache


def kind_module(name: str):
    """``bench/kinds/<name>.py``: what a configuration of that kind
    builds, serves and checks."""
    return importlib.import_module("kinds." + name)


def committed_page_bytes(path: str) -> int:
    """Bytes of the deduplicated page set the store committed, read from
    the SQLite file by the benchmark itself."""
    con = sqlite3.connect(f"file:{path}?mode=ro", uri=True)
    try:
        return int(con.execute(
            "SELECT SUM(LENGTH(data)) FROM pages").fetchone()[0])
    finally:
        con.close()


class StagedSizes:
    """Counts the sizes of the page stacks the transfer engines of the
    given page pools stage (``TransferEngine.stage``), for the whole
    run."""

    def __init__(self, pools):
        self.counts = Counter()
        for pool in pools:
            self._note(pool.transfer)

    def _note(self, transfer) -> None:
        stage = transfer.stage

        def noted(pids):
            pg = stage(pids)
            if pg is not None:
                self.counts[len(pg.index)] += 1
            return pg

        transfer.stage = noted


#: how far from a staged size seen in warm-up the window's sizes stray
STAGED_SPREAD = 8


def warm_staged_gathers(pool, sizes) -> None:
    """Compile the transfer engine's gather from a staged stack
    (``PendingGroup.dev[rows]``, one program per stack size and index
    bucket) for every size within :data:`STAGED_SPREAD` of one the
    warm-up staged, on the pool's device.  A slab smaller than the fleet
    stages groups whose size settles into a cycle but now and then
    strays by a page or two; without this each new size compiles inside
    the window, and the run is not correct (``window_compiles``)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.dedup_embedding import LANES

    def pow2(n):
        b = 1
        while b < n:
            b <<= 1
        return b

    for s in sorted(sizes):
        for k in range(max(1, s - STAGED_SPREAD), s + STAGED_SPREAD + 1):
            stack = jnp.zeros((k, pool.page_rows, LANES), pool.dtype)
            if pool.device is not None:
                stack = jax.device_put(stack, pool.device)
            buckets = {pow2(m) for m in range(max(1, k - STAGED_SPREAD),
                                               k + 1)}
            for b in sorted(buckets):
                stack[jnp.zeros(b, jnp.int32)].block_until_ready()


def counters(engine) -> dict:
    s, pool = engine.stats, engine.server.pool
    return {"hits": pool.hits, "misses": pool.misses,
            "batches": s.batches, "device_batches": s.device_batches,
            "dense_fallbacks": s.dense_fallbacks,
            "degraded_batches": s.degraded_batches}


def metric_reader(name: str):
    """``bench/metrics/<name>.py``.  A name may hold dots: a metric with
    no file of its own, such as ``docs_per_s.paging``, is read by the
    file of the name without its last part (``docs_per_s.py``); the
    suffix gives a group of cells a bound or a ``moves`` of its own."""
    base = name
    path = os.path.join(BENCH, "metrics", base + ".py")
    while not os.path.exists(path) and "." in base:
        base = base.rsplit(".", 1)[0]
        path = os.path.join(BENCH, "metrics", base + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + base, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec: dict, cell: dict, traced: bool):
    """``[(name, unit)]`` the cell reports in this kind of run."""
    group = spec["per_layer"] if traced else spec["end_to_end"]
    return [(m["name"], m["unit"]) for m in group
            if "workloads" not in m or cell["name"] in m["workloads"]]


def sample(done, k: int, seed: int) -> list:
    """``k`` answered requests drawn from the seed (all when fewer)."""
    from traffic import SAMPLE, seed_rng
    if len(done) <= k:
        return list(done)
    idx = seed_rng(seed, SAMPLE).choice(len(done), k, replace=False)
    return [done[i] for i in sorted(idx)]


def run(argv=None, *, require_tpu: bool = True, fault=None) -> dict:
    """One run; returns the result object (also printed).  ``fault``, for
    the harness's own tests, is called with the engine once it is built
    and may break the served path underneath."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec, cell, cfg, mix = load_cell(args.workload)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    kind = kind_module(cfg.get("kind", "embedding"))
    import jax
    if require_tpu:
        devices = tpu_devices(cell["chips"])
    else:
        devices = jax.devices()
    dev = devices[0]
    cache = setup_jax(cfg)
    info(f"[device] platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devices)} compile_cache={cache}")
    events = CompileEvents()

    from kinds import device_pools
    from loop import ClosedLoop

    # ---------------------------------------------------------- set-up --
    os.makedirs(SCRATCH, exist_ok=True)
    db_path = os.path.join(SCRATCH, cell["name"] + ".db")
    for suffix in ("", "-journal", "-wal", "-shm"):
        if os.path.exists(db_path + suffix):
            os.remove(db_path + suffix)
    url = "sqlite:///" + db_path
    t = time.perf_counter()
    state = kind.build(cfg, args.seed, url)
    t_build = time.perf_counter() - t

    t = time.perf_counter()
    db, engine = kind.open_engine(url, cfg, mix, state, devices)
    store = db.store
    server = engine.server
    pools = device_pools(server)
    cap = sum(p.capacity for p in pools)
    mode = ",".join(sorted({p.mode() for p in pools}))
    if fault is not None:
        fault(engine)
    staged = StagedSizes(pools)
    t_reopen = time.perf_counter() - t

    t = time.perf_counter()
    window_reqs, warm_reqs = kind.requests(cfg, mix, state, args.seed)
    kind.warm(engine, cfg, mix, warm_reqs)
    for pool in pools:
        warm_staged_gathers(pool, set(staged.counts))
    staged.counts.clear()
    loop = ClosedLoop(engine, window_reqs, mix["callers"], kind.send,
                      kind.answer)
    gc.collect()
    gc.freeze()
    t_warm = time.perf_counter() - t
    compile_setup_s = events.seconds
    c0 = counters(engine)
    spans = None
    trace_dir = os.path.join(SCRATCH, "trace", cell["name"])
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        spans = kind.spans(engine, db)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
    e0 = events.snapshot()
    setup_s = time.perf_counter() - T_START
    info(f"[setup] setup_s={setup_s} build_s={t_build} reopen_s={t_reopen} "
         f"warm_s={t_warm} compile_s={compile_setup_s} "
         f"slab_pages={cap} store_pages={store.num_pages()} mode={mode}")

    # ---------------------------------------------------------- window --
    if args.trace:
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            t0, t_end, done = loop.serve_for(args.seconds)
        jax.profiler.stop_trace()
    else:
        t0, t_end, done = loop.serve_for(args.seconds)
    e1 = events.snapshot()
    c1 = counters(engine)
    gc.unfreeze()
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    delta = {k: c1[k] - c0[k] for k in c0}
    failed_batches = kind.failed_batches(delta)
    window_compiles = e1[0] - e0[0]
    sizes = ",".join(f"{k}x{v}" for k, v in sorted(staged.counts.items()))
    info(f"[window] seconds={t_end - t0} answered={len(done)} "
         f"new_programs={window_compiles} backend_compiles={e1[1] - e0[1]} "
         f"cache_loads={e1[2] - e0[2]} requests_wrapped={loop.wrapped} "
         f"programs={','.join(map(str, events.names[e0[0]:e1[0]]))} "
         f"staged_sizes={sizes}")
    info(f"[counters] batches={delta['batches']} "
         f"device_batches={delta['device_batches']} "
         f"dense_fallbacks={delta['dense_fallbacks']} "
         f"degraded_batches={delta['degraded_batches']} "
         f"pool_hits={delta['hits']} pool_misses={delta['misses']}")
    store_bytes = committed_page_bytes(db_path)
    fields = kind.fields(cfg, mix, state, done, spans)
    picked = sample(done, mix["sample_requests"], args.seed)
    answered = [(d.sent, d.done) for d in done]

    # free the program's state before the reference runs
    del engine, server, pools, loop, store, spans, staged
    db.close()
    del db
    gc.collect()

    # ----------------------------------------------------------- check --
    t = time.perf_counter()
    checks = kind.check(cfg, args.seed, picked, state)
    info(f"[check] sampled_requests={len(picked)} "
         f"reference_s={time.perf_counter() - t}")
    checks["fallback_batches"] = {"value": failed_batches, "limit": 0}
    checks["window_compiles"] = {"value": window_compiles, "limit": 0}
    correct = bool(picked) and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values())

    # --------------------------------------------------------- metrics --
    data = SimpleNamespace(
        cfg=cfg, mix=mix, cell=cell, setup_s=setup_s,
        window_s=t_end - t0,
        requests=len(answered),
        latencies_s=[b - a for a, b in answered],
        store_bytes=store_bytes, counters=delta, peaks=None, trace=None,
        lo=t0, hi=t_end, **fields)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(answered),
           "failed": failed_batches}
    breakdown = None
    if args.trace:
        import peaks
        import reduce
        data.peaks = peaks.peaks(dev.device_kind) if require_tpu else \
            next(iter(peaks.PEAKS.values()))
        tr = reduce.load(trace_dir)
        data.trace = tr
        data.lo, data.hi = reduce.window_of(tr.spans)
        device["busy_s"] = reduce.busy_seconds(tr.device, data.lo, data.hi)
        device["window_s"] = data.hi - data.lo
        breakdown = {
            "device_ops": reduce.top(reduce.op_seconds(
                tr.device, data.lo, data.hi)),
            "idle_gaps": reduce.top(reduce.gaps_by_span(
                tr.device, tr.spans, data.lo, data.hi)),
        }
    metrics = {}
    for name, unit in cell_metrics(spec, cell, bool(args.trace)):
        value = metric_reader(name).read(data)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    out["metrics"] = metrics
    out["device"] = device
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for k, v in checks.items():
        print(f"[check] {k}={v['value']} limit={v['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return out


def main(argv=None) -> int:
    try:
        run(argv)
    except Refused as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
