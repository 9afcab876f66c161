"""The one traffic generator: requests made from a mix file and the seed.

A mix (``bench/traffic/<name>.json``) gives the closed loop's callers,
the request shape (documents per request, tokens per document), how the
model of each request is drawn, how much of the fleet the slab holds,
and how many requests warm the system up.  Every seed gets the same
amounts: the models come from a fixed *deck* (each model's count in a
deck of ``deck`` requests is fixed by the popularity law), dealt in a
seeded order, and every request has the same shape.  The seed draws only
the order of the deck and the tokens.

Documents follow the fleet's classification task: a document of class
``y`` draws three quarters of its tokens from the family's class-``y``
token set and the rest uniformly from the vocabulary.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple

import numpy as np


class Request(NamedTuple):
    index: int
    model: str
    docs: np.ndarray          # [docs_per_request, doc_len] int32


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one stream of a run's ``--seed`` (any whole
    number; its 32-bit words feed a SeedSequence)."""
    s = int(seed) % (1 << 64)
    return np.random.default_rng([s & 0xFFFFFFFF, s >> 32, stream])


def deck_counts(weights: List[float], deck: int) -> List[int]:
    """Largest-remainder apportionment of ``deck`` requests to models
    by ``weights``; every model keeps at least one."""
    w = np.asarray(weights, np.float64)
    raw = w / w.sum() * deck
    counts = np.maximum(np.floor(raw).astype(int), 1)
    for i in np.argsort(-(raw - np.floor(raw)), kind="stable"):
        if counts.sum() >= deck:
            break
        counts[i] += 1
    return [int(c) for c in counts]


def popularity_weights(mix: dict, n_models: int) -> List[float]:
    pop = mix["popularity"]
    if pop["kind"] == "zipf":
        return [1.0 / (r + 1) ** float(pop["alpha"]) for r in range(n_models)]
    raise ValueError(f"unknown popularity kind {pop['kind']!r}")


def model_sequence(mix: dict, models: List[str], n: int,
                   rng: np.random.Generator) -> List[str]:
    """``n`` models: whole decks, each dealt in a seeded order, or with
    popularity kind ``fixed`` the ranks in ``order``, cycled, so the
    order of models is the same for every seed too."""
    pop = mix["popularity"]
    if pop["kind"] == "fixed":
        return [models[pop["order"][i % len(pop["order"])]]
                for i in range(n)]
    counts = deck_counts(popularity_weights(mix, len(models)),
                         mix["popularity"]["deck"])
    deck = np.repeat(np.arange(len(models)), counts)
    out: List[str] = []
    while len(out) < n:
        out.extend(models[i] for i in rng.permutation(deck))
    return out[:n]


def make_docs(token_class: np.ndarray, classes: int, n_docs: int,
              doc_len: int, rng: np.random.Generator) -> np.ndarray:
    """``[n_docs, doc_len]`` int32 token ids of one request."""
    vocab = len(token_class)
    n_other = doc_len // 4
    labels = rng.integers(0, classes, n_docs)
    docs = np.empty((n_docs, doc_len), np.int32)
    for c in range(classes):
        pool = np.flatnonzero(token_class == c)
        rows = np.flatnonzero(labels == c)
        docs[rows, :doc_len - n_other] = pool[rng.integers(
            0, len(pool), (len(rows), doc_len - n_other))]
    docs[:, doc_len - n_other:] = rng.integers(0, vocab, (n_docs, n_other))
    return docs


WINDOW, WARMUP, SAMPLE = 1, 2, 3      # seed streams


def make_requests(mix: dict, cfg: dict, token_class: Dict[str, np.ndarray],
                  seed: int, n: int, stream: int = WINDOW) -> List[Request]:
    """The first ``n`` requests of one of the mix's streams (the window's
    or the warm-up's) under ``seed``."""
    rng = seed_rng(seed, stream)
    models = model_sequence(mix, cfg["popularity"], n, rng)
    return [Request(i, m, make_docs(token_class[m], cfg["classes"],
                                    mix["docs_per_request"],
                                    mix["doc_len"], rng))
            for i, m in enumerate(models)]
