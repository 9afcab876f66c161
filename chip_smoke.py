#!/usr/bin/env python3
"""Chip smoke test: the paper's serving path once, end to end, on a TPU.

    python chip_smoke.py                # one chip: embedding fleet + LM
    python chip_smoke.py --four-chips   # only: 4-shard serving vs 1 shard

It runs in one process and starts none.  It refuses to run unless JAX's
first device is a TPU, and every check raises, so any failure exits
non-zero.  On success the last line of standard output is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``; the
``[info]`` lines before it are information only, the ``[check]`` lines
say what was verified.

Embedding phase (the main path).  A ``SyntheticTextTask(vocab, 4096)``
fleet of four fine-tuned variants (d=4096 is deepseek-7b's ``d_model``)
is registered with Alg. 1 at ``build_store``'s 64x64 blocks and 8 blocks
per page, committed to ``sqlite:///<checkout>/.scratch/chip_smoke/
fleet.db`` and reopened with ``DedupDB.open``.  ``WeightServer(backend=
"device")`` faults pages from SQLite into the HBM slab and
``EmbeddingServingEngine`` answers a few batches through the Pallas
gather, then ``ServingFrontend(capture=True)`` serves a few dozen
requests.  Vocab is cut from 102400 to 8192 because the store build
runs one Python LSH query per block.

LM phase.  ``launch.serve``'s reduced deepseek LM on ``--backend
device`` for two batches: slab unblock, prefill and decode on the TPU.

Four-chip phase (``--four-chips`` only).  The same fleet at 4 shards
with sharer placement against 1 shard: four distinct chips, one shard
slab on each, and logits equal to the one-shard run.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SCRATCH = os.path.join(ROOT, ".scratch", "chip_smoke")

D_MODEL = 4096               # configs/deepseek_7b.py d_model
FULL_VOCAB = 102_400         # configs/deepseek_7b.py vocab
VOCAB = 8192                 # the one reduction: store build time
VARIANTS = 4
BLOCK = (64, 64)             # launch/serve.build_store defaults
BLOCKS_PER_PAGE = 8
ENGINE_BATCHES = 4
DOCS_PER_BATCH = 16          # 16 docs x 16 tokens = a 256-id bucket
REQUESTS = 32
DOCS_PER_REQUEST = 2
SEED = 0

#: TPU default precision for a float32 matmul rounds both operands to
#: bfloat16 (unit roundoff 2^-9) and accumulates in float32, so a dot
#: product errs by at most ~2 * 2^-9 * sum|x||w|.  The bound used is
#: twice that: 2^-7 * (|x| @ |w|), per logit.
LOGIT_REL_BOUND = 2.0 ** -7

def info(msg: str) -> None:
    print(f"[info] {msg}", flush=True)


def check(msg: str) -> None:
    print(f"[check] {msg}", flush=True)


def tpu_devices(count: int):
    """The TPU devices, or exit non-zero naming the platform found."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX's platform is "
                 f"{platform!r} ({len(devs)} device(s))")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips; JAX sees "
                 f"{len(devs)}")
    return devs


class CompileClock:
    """Sums the seconds JAX reports spending in compilation (tracing,
    lowering and the backend compile) once :meth:`install` is called."""

    def __init__(self):
        self.seconds = 0.0

    def install(self) -> "CompileClock":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def _on_event(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


class phase:
    """Times a phase: wall seconds and compile seconds inside it."""

    def __init__(self, name: str, compiles: CompileClock):
        self.name, self.compiles = name, compiles

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.compiles.seconds
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            info(f"phase={self.name} wall_s={time.perf_counter() - self.t0} "
                 f"compile_s={self.compiles.seconds - self.c0}")
        return False


# ------------------------------------------------------------------ fleet --
def build_fleet(vocab: int = VOCAB, d: int = D_MODEL):
    """Register the variant fleet, commit it to SQLite, reopen it live.
    Returns ``(task, db, heads)``."""
    from repro.data.pipeline import SyntheticTextTask
    from repro.db import DedupDB
    from repro.launch.serve import build_store

    task = SyntheticTextTask(vocab=vocab, d=d, seed=SEED)
    store, heads = build_store(task, VARIANTS, block_shape=BLOCK,
                               blocks_per_page=BLOCKS_PER_PAGE)
    shutil.rmtree(SCRATCH, ignore_errors=True)
    os.makedirs(SCRATCH)
    url = "sqlite:///" + os.path.join(SCRATCH, "fleet.db")
    store.save(url)
    db = DedupDB.open(url)
    info(f"fleet vocab={vocab} (published {FULL_VOCAB}) d={d} "
         f"variants={VARIANTS} block={BLOCK} blocks_per_page="
         f"{BLOCKS_PER_PAGE} pages={db.store.num_pages()} "
         f"dense_bytes={store.dense_bytes()} "
         f"dedup_bytes={db.store.storage_bytes()} store={url}")
    return task, db, heads


def slab_capacity(store) -> int:
    """Pages for the slab: two-stage packing spreads a row's 64 stripes
    over most of its model's pages, so one batch touches nearly a whole
    model.  The slab holds the largest model plus half of the pages no
    single model covers — less than the fleet, so variant switches
    fault pages in from SQLite."""
    biggest = max(len(store.model_pages(m)) for m in store.dedup.models)
    return biggest + (store.num_pages() - biggest) // 2


def host_logits(store, model: str, docs, head):
    """The plain float32 reference: host rows from the store, mean pool,
    numpy head.  Returns ``(feats, logits)``."""
    import numpy as np
    rows = np.unique(docs)
    emb = store.materialize_rows(model, "embedding", rows)
    feats = emb[np.searchsorted(rows, docs)].mean(axis=1)
    return feats, feats @ head


class LogitCheck:
    """Accumulates device-vs-host logit comparisons under the stated
    bound; raises on the first violation."""

    def __init__(self):
        self.rows = self.undecided = 0
        self.worst = 0.0

    def __call__(self, got, feats, ref, head, what: str) -> None:
        import numpy as np
        got = np.asarray(got, np.float32)
        if got.shape != ref.shape or not np.isfinite(got).all():
            raise AssertionError(f"{what}: logits {got.shape} not finite "
                                 f"or not {ref.shape}")
        bound = LOGIT_REL_BOUND * (np.abs(feats) @ np.abs(head)) + 1e-30
        ratio = float((np.abs(got - ref) / bound).max())
        self.worst = max(self.worst, ratio)
        if ratio > 1.0:
            raise AssertionError(f"{what}: logits off by {ratio} x the "
                                 f"bound")
        # argmax must agree wherever the reference margin exceeds what
        # the two logits' bounds could swap
        top2 = np.sort(ref, axis=1)[:, -2:]
        margin = top2[:, 1] - top2[:, 0]
        decided = margin > 2.0 * bound.max(axis=1)
        bad = decided & (got.argmax(1) != ref.argmax(1))
        if bad.any():
            raise AssertionError(f"{what}: argmax differs on "
                                 f"{int(bad.sum())} decided rows")
        self.rows += len(ref)
        self.undecided += int((~decided).sum())


def check_counters(stats, what: str) -> None:
    if stats.batches == 0 or stats.device_batches != stats.batches \
            or stats.dense_fallbacks or stats.degraded_batches:
        raise AssertionError(
            f"{what}: batches={stats.batches} device_batches="
            f"{stats.device_batches} dense_fallbacks={stats.dense_fallbacks}"
            f" degraded_batches={stats.degraded_batches}")
    check(f"{what}: device_batches={stats.device_batches} "
          f"batches={stats.batches} dense_fallbacks=0 degraded_batches=0")


def engine_batches(task, v_of_batch):
    """Seeded engine batches: ``[(model, docs)]``."""
    out = []
    for b, v in enumerate(v_of_batch):
        docs, _ = task.sample(DOCS_PER_BATCH, variant=v, seed=SEED + 100 + b)
        out.append((f"word2vec-v{v}", docs))
    return out


# ------------------------------------------------------------------ phases --
def embedding_phase(task, db, heads) -> None:
    import numpy as np
    from repro.serving.frontend import BatchComputeModel, ServingFrontend
    from repro.serving.traffic import OpenLoopTraffic

    store = db.store
    cap = slab_capacity(store)
    engine = db.serve_embedding(heads, capacity_pages=cap,
                                compute_backend="device")
    server, pool = engine.server, engine.server.device_pool
    if pool.mode() != "pallas":
        raise AssertionError(f"pool mode is {pool.mode()!r}, not 'pallas'")
    check("pool mode=pallas")
    info(f"slab pages={cap} of {store.num_pages()} "
         f"slab_bytes={pool.slab.nbytes}")
    logits = LogitCheck()

    # engine batches, one per variant, each checked against the host
    for model, docs in engine_batches(task, range(ENGINE_BATCHES)):
        engine.submit(model, docs)
        engine.run(max_batches=1)
        feats, ref = host_logits(store, model, docs, heads[model])
        logits(engine.last_logits, feats, ref, heads[model],
               f"engine batch {model}")
    info(f"slab loads={pool.loads} evicts={pool.evicts}")

    # the gather copies float32 bytes: rows must be bit-exact
    rng = np.random.default_rng(SEED + 7)
    for v in range(VARIANTS):
        model = f"word2vec-v{v}"
        rows = np.unique(rng.integers(0, task.vocab, 64))
        pages = server.embedding_rows_pages(model, "embedding", rows)
        server.access_pages_grouped(model, pages)
        got = server.device_gather_rows(model, "embedding", rows,
                                        pages=pages)
        if got is None:
            raise AssertionError(f"{model}: gather found pages missing")
        want = store.materialize_rows(model, "embedding", rows)
        if not np.array_equal(np.asarray(got), want):
            raise AssertionError(f"{model}: gathered rows differ from "
                                 f"store.materialize_rows")
    check(f"gathered rows bit-exact: {VARIANTS} variants x <=64 rows")

    # a few dozen requests through the front end
    names = [f"word2vec-v{v}" for v in range(VARIANTS)]

    def payload(model, rid, prng):
        v = int(model.rsplit("-v", 1)[1])
        docs, _ = task.sample(DOCS_PER_REQUEST, variant=v,
                              seed=SEED + 1000 + rid)
        return docs

    reqs = OpenLoopTraffic(names, rate=100.0, zipf_alpha=1.1, slo_s=600.0,
                           seed=SEED, payload_fn=payload).generate(REQUESTS)
    # a modelled compute clock keeps batch formation deterministic: the
    # SLO policy then never sheds on a drifting compute estimate
    fe = ServingFrontend(engine, max_batch=8, capture=True,
                         compute_model=BatchComputeModel())
    fe.run(reqs)
    fe.assert_ledger_conserved()
    led = fe.ledger
    if len(led.served) != REQUESTS or led.shed or led.in_flight:
        raise AssertionError(f"frontend served {len(led.served)} of "
                             f"{REQUESTS} (shed {len(led.shed)}, in flight "
                             f"{len(led.in_flight)})")
    for r in reqs:
        feats, ref = host_logits(store, r.model, r.payload, heads[r.model])
        logits(fe.results[r.rid], feats, ref, heads[r.model],
               f"request {r.rid}")
    check(f"request ledger conserved: offered={len(led.offered)} "
          f"served={len(led.served)} shed=0 in_flight=0 "
          f"dispatches={len(fe.dispatched)}")
    check_counters(engine.stats, "embedding engine")
    check(f"logits within {LOGIT_REL_BOUND} x (|x| @ |w|) of the numpy "
          f"head: rows={logits.rows} worst_ratio={logits.worst} "
          f"argmax agrees on all decided rows "
          f"(undecided={logits.undecided})")


def lm_phase() -> None:
    from repro.launch.serve import main as serve_main
    stats, server = serve_main(["--engine", "lm", "--backend", "device",
                                "--batches", "2", "--seed", str(SEED)])
    mode = server.device_pool.mode()
    if mode != "pallas":
        raise AssertionError(f"LM pool mode is {mode!r}, not 'pallas'")
    check_counters(stats, "lm engine")


def four_chip_phase(task, db, heads, devices) -> None:
    import numpy as np
    cap = slab_capacity(db.store)
    batches = engine_batches(task, [v % VARIANTS for v in range(8)])
    runs = {}
    for shards in (1, 4):
        engine = db.serve_embedding(heads, capacity_pages=cap,
                                    compute_backend="device",
                                    shards=shards, placement="sharers")
        out = []
        for model, docs in batches:
            engine.submit(model, docs)
            engine.run(max_batches=1)
            out.append(np.array(engine.last_logits))
        check_counters(engine.stats, f"{shards}-shard engine")
        runs[shards] = (engine, out)
    sharded = runs[4][0].server.sharded
    slab_devices = [d for p in sharded.pools for d in p.slab.devices()]
    if len(slab_devices) != 4 or set(slab_devices) != set(devices[:4]):
        raise AssertionError(f"shard slabs on {slab_devices}, not one on "
                             f"each of {list(devices[:4])}")
    check(f"4 shard slabs on 4 distinct devices: "
          f"{[d.id for d in slab_devices]}")
    for b, (a, c) in enumerate(zip(runs[1][1], runs[4][1])):
        if not np.array_equal(a, c):
            raise AssertionError(f"batch {b}: 4-shard logits differ from "
                                 f"1-shard by {np.abs(a - c).max()}")
    check(f"4-shard logits equal to 1-shard logits: "
          f"{len(batches)} batches")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-shard serving phase and its "
                         "1-shard comparison (needs four TPU chips)")
    args = ap.parse_args(argv)
    devices = tpu_devices(4 if args.four_chips else 1)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.cache import enable_compile_cache
    info(f"compile cache: {enable_compile_cache()}")
    compiles = CompileClock().install()
    dev = devices[0]
    info("[info] lines are information only; [check] lines are verified")
    info(f"device platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devices)}")
    t0 = time.perf_counter()
    with phase("build_fleet", compiles):
        task, db, heads = build_fleet()
    if args.four_chips:
        with phase("four_chips", compiles):
            four_chip_phase(task, db, heads, devices)
    else:
        with phase("embedding", compiles):
            embedding_phase(task, db, heads)
        with phase("lm", compiles):
            lm_phase()
    db.close()
    stats = dev.memory_stats() or {}
    info(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
         f"total_wall_s={time.perf_counter() - t0}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
