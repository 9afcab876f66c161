"""The embedding-bag fleet: the kind of every configuration without ``kind``.

Variants of a family's embedding table ``[vocab, d]`` (``fleet.py``) are
served by ``DedupDB.serve_embedding``: a request is ``[docs, doc_len]``
token ids (``traffic.py``), its answer the logits of its documents'
mean-pooled rows through the variant's linear head, checked against
``references/<reference>.py`` (``check.py``).  The contract each function
keeps is in ``kinds/__init__.py``.

A mix may shard the slab: ``shards`` (default 1) goes to
``serve_embedding``, with the program's own placement, and each shard's
slab holds ``slab_pages`` pages (the program's capacity is per shard).
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import numpy as np

import fleet
import traffic
from check import compare
from kinds import device_pools
from loop import ClosedLoop


class Fleet(NamedTuple):
    heads: Dict[str, np.ndarray]        # handed to the engine
    ref_heads: Dict[str, np.ndarray]    # the reference's copies
    token_class: Dict[str, np.ndarray]


def build(cfg: dict, seed: int, url: str) -> Fleet:
    heads, token_class = fleet.build_fleet(cfg, seed, url)
    return Fleet(heads, {m: h.copy() for m, h in heads.items()},
                 token_class)


def slab_pages(store, cfg: dict, mix: dict) -> int:
    """The slab's capacity in pages (each shard's), as the mix sizes it:
    ``fleet``, the whole store; ``family_plus``, the largest family and
    ``other_frac`` of the pages only the others hold; ``fleet_frac``,
    ``frac`` of the store's pages."""
    slab = mix["slab"]
    if slab["kind"] == "fleet":
        return store.num_pages()
    if slab["kind"] == "family_plus":
        fams = [set().union(*(store.model_pages(m) for m in names))
                for names in fleet.variant_names(cfg)]
        big = max(fams, key=len)
        rest = set().union(*(f for f in fams if f is not big)) - big
        return len(big) + int(round(slab["other_frac"] * len(rest)))
    if slab["kind"] == "fleet_frac":
        return max(1, int(round(slab["frac"] * store.num_pages())))
    raise ValueError(f"unknown slab kind {slab['kind']!r}")


def open_engine(url: str, cfg: dict, mix: dict, state: Fleet, devices):
    """Reopen the committed store as users open one and build the served
    engine on it: the slab sized by the mix, ``round_robin``, overlapped
    fetches, the device backend.  On a TPU every slab's kernels are the
    Pallas ones."""
    from repro.db import DedupDB
    db = DedupDB.open(url)
    engine = db.serve_embedding(state.heads,
                                capacity_pages=slab_pages(db.store, cfg, mix),
                                scheduler="round_robin", overlap=True,
                                compute_backend="device",
                                shards=mix.get("shards", 1))
    if devices[0].platform == "tpu":
        modes = {p.mode() for p in device_pools(engine.server)}
        if modes != {"pallas"}:
            raise RuntimeError(f"slab kernel mode is {sorted(modes)}, "
                               f"not 'pallas'")
    return db, engine


def requests(cfg: dict, mix: dict, state: Fleet, seed: int):
    window = traffic.make_requests(mix, cfg, state.token_class, seed,
                                   mix["pregenerate"])
    warmup = traffic.make_requests(mix, cfg, state.token_class, seed,
                                   mix["warmup_requests"],
                                   stream=traffic.WARMUP)
    return window, warmup


def warm(engine, cfg: dict, mix: dict, warmup) -> None:
    """Bring the served path to its steady state: fill the slab when it
    holds the fleet, read every page no request would (``fault_all``),
    and serve the warm-up requests, which cover every model (each model's
    head uploads at its first request).  The second pass starts its
    callers together from the steady state, as the window does, so the
    window's first batches find their programs compiled too."""
    store = engine.server.store
    if mix["slab"]["kind"] == "fleet":
        for m in cfg["popularity"]:
            engine.server.access_pages_grouped(m, store.model_pages(m))
    store.fault_all()
    ClosedLoop(engine, warmup, mix["callers"], send, answer).serve_count(
        len(warmup))
    ClosedLoop(engine, warmup, mix["callers"], send, answer).serve_count(
        2 * mix["callers"])


def send(engine, req) -> None:
    engine.submit(req.model, req.docs)


def answer(engine):
    return engine.last_logits


def spans(engine, db):
    from spans import Spans
    return Spans(engine, db)


def failed_batches(counters: dict) -> int:
    """Batches served off the device path: host fallbacks, degraded
    batches, and any batch the engine did not count as a device one."""
    return (counters["dense_fallbacks"] + counters["degraded_batches"]
            + (counters["batches"] - counters["device_batches"]))


def check(cfg: dict, seed: int, picked, state: Fleet) -> dict:
    result = compare(cfg, seed, picked, state.ref_heads)
    print(f"[check] logits={result['logits']}", flush=True)
    return {"logit_err": {"value": result["logit_err"],
                          "limit": cfg["checks"]["logit_err"]["limit"]}}


def fields(cfg: dict, mix: dict, state: Fleet, done, spans) -> dict:
    return {"variants": len(cfg["popularity"]),
            "docs": sum(d.request.docs.shape[0] for d in done),
            "width": cfg[cfg["width_key"]],
            "gather_ids": list(spans.gather_ids) if spans is not None
            else []}
