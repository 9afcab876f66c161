#!/usr/bin/env python3
"""Per-seed counts of a cell's work at a reduced vocabulary (any platform).

For each seed: the cell's set-up (fleet, store, slab, warm-up) and then a
fixed number of requests, counting the store's pages, the pages each
request touches, the slab misses per request and the documents per
request.  A count that moves with the seed means the seed changes the
amount of work, not only its values.

    JAX_PLATFORMS=cpu python3 bench/seed_table.py --workload \\
        ds7b-embed.paging --vocab 2048 --requests 64 --seeds 1 2 3

Counts only: no time is taken.  On the CPU the slab is the program's
host mirror, whose page accounting is the device slab's.
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


def counts(cell_name: str, vocab: int, n: int, seed: int, tmp: str) -> dict:
    import jax
    import run
    import traffic
    from kinds import embedding as kind
    from loop import ClosedLoop

    _, cell, cfg, mix = run.load_cell(cell_name)
    scale = vocab / cfg["vocab_size"]
    cfg["touched_row_blocks"] = max(1, round(cfg["touched_row_blocks"]
                                             * scale))
    cfg["vocab_size"] = vocab
    url = "sqlite:///" + os.path.join(tmp, f"{cell_name}.{seed}.db")
    state = kind.build(cfg, seed, url)
    db, engine = kind.open_engine(url, cfg, mix, state, jax.devices())
    store, server = db.store, engine.server
    _, warmup = kind.requests(cfg, mix, state, seed)
    kind.warm(engine, cfg, mix, warmup)
    reqs = traffic.make_requests(mix, cfg, state.token_class, seed, n)
    h0, m0 = server.pool.hits, server.pool.misses
    done = ClosedLoop(engine, reqs, mix["callers"], kind.send,
                      kind.answer).serve_count(n)
    h1, m1 = server.pool.hits, server.pool.misses
    db.close()
    return {"seed": seed, "touched_row_blocks": cfg["touched_row_blocks"],
            "fleet_pages": store.num_pages(),
            "pages_per_req": (h1 - h0 + m1 - m0) / len(done),
            "misses_per_req": (m1 - m0) / len(done),
            "docs_per_req": sum(len(d.request.docs) for d in done)
            / len(done)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    tmp = os.path.join(ROOT, ".scratch", "seed_table")
    os.makedirs(tmp, exist_ok=True)
    try:
        print("| seed | fleet pages | pages/req | misses/req | docs/req |")
        print("| --- | --- | --- | --- | --- |")
        for seed in args.seeds:
            c = counts(args.workload, args.vocab, args.requests, seed, tmp)
            print(f"| {seed} | {c['fleet_pages']} | {c['pages_per_req']:.2f}"
                  f" | {c['misses_per_req']:.2f} | {c['docs_per_req']:.0f} |",
                  flush=True)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
