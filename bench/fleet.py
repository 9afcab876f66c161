"""The variant fleet a configuration describes, made from the seed.

This is the benchmark's own copy of the repo's synthetic fleet
(``SyntheticTextTask`` and ``launch/serve.py``'s ``build_store``), so a
change to the program cannot move what the benchmark serves.

A *family* is one base embedding table ``[vocab, d]`` (N(0, 0.05)) and
its fine-tuned variants.  A variant adds N(0, delta_std) to the rows of
``touched_row_blocks`` whole row blocks; a family's variants touch
disjoint row blocks, so every seed gives the same number of distinct
blocks and pages, and only the values move with the seed.  Untouched
blocks are bit-equal to the base, and the fixed LSH radius lies far below
the distance of a touched block from its base block, so deduplication
merges exactly the equal blocks: a served row equals the variant's own
row, which is what the plain reference computes from.

Each variant has a linear head ``[d, classes]`` trained by gradient
descent on documents drawn from its family's class-token sets.  A
family's arrays are made on the device in one jitted call.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple

import numpy as np

FAMILY_STRIDE = 1_000_003      # keeps the families' key streams apart
HEAD_DOCS = 512                # documents the head is trained on
HEAD_STEPS = 200
HEAD_LR = 0.5


class Family(NamedTuple):
    name: str
    variants: List[str]        # model names, in registration order
    weights: object            # device [n_var, vocab, d] float32
    heads: object              # device [n_var, d, classes] float32
    token_class: np.ndarray    # host [vocab] int32


def family_seed(seed: int, index: int) -> int:
    """Family ``index``'s generator seed under a run's ``--seed`` (any
    whole number; folded into 63 bits)."""
    return (int(seed) * FAMILY_STRIDE + index) % (1 << 63)


def seed_key(seed: int):
    """A jax key from a whole number of up to 63 bits (``jax.random.key``
    alone keeps only the low 32)."""
    import jax
    s = int(seed) % (1 << 63)
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


def variant_names(cfg: dict) -> List[List[str]]:
    """Model names per family: ``<family>-v<k>``."""
    return [[f"{fam}-v{k}" for k in range(cfg["variants_per_family"])]
            for fam in cfg["families"]]


@functools.lru_cache(maxsize=None)
def _family_fn(vocab: int, d: int, classes: int, n_var: int,
               touched: int, row_block: int, doc_len: int,
               delta_std: float):
    import jax
    import jax.numpy as jnp

    n_rb = vocab // row_block

    def make(key):
        kb, kt, kp, kd, kh = jax.random.split(key, 5)
        base = jax.random.normal(kb, (vocab, d), jnp.float32) * 0.05
        token_class = jax.random.randint(kt, (vocab,), 0, classes)
        blocks = jax.random.permutation(kp, n_rb)[:n_var * touched]
        blocks = blocks.reshape(n_var, touched)
        rb_of_row = jnp.arange(vocab) // row_block

        def variant(v):
            hit = jnp.isin(rb_of_row, blocks[v])[:, None]
            delta = jax.random.normal(jax.random.fold_in(kd, v),
                                      (vocab, d), jnp.float32) * delta_std
            return jnp.where(hit, base + delta, base)

        weights = jnp.stack([variant(v) for v in range(n_var)])

        def head(v, emb):
            k1, k2, k3 = jax.random.split(jax.random.fold_in(kh, v), 3)
            labels = jax.random.randint(k1, (HEAD_DOCS,), 0, classes)
            # main tokens from the label's class set, a quarter uniform
            logits = jnp.where(token_class[None, :] == labels[:, None],
                               0.0, -jnp.inf)
            main = jax.random.categorical(
                k2, logits[:, None, :],
                shape=(HEAD_DOCS, doc_len - doc_len // 4))
            other = jax.random.randint(k3, (HEAD_DOCS, doc_len // 4), 0,
                                       vocab)
            docs = jnp.concatenate([main, other], axis=1)
            feats = emb[docs].mean(axis=1)
            onehot = jax.nn.one_hot(labels, classes, dtype=jnp.float32)

            def step(_, w):
                p = jax.nn.softmax(
                    jnp.dot(feats, w, precision="highest"), axis=1)
                g = jnp.dot(feats.T, p - onehot,
                            precision="highest") / HEAD_DOCS
                return w - HEAD_LR * g

            return jax.lax.fori_loop(0, HEAD_STEPS, step,
                                     jnp.zeros((d, classes), jnp.float32))

        heads = jnp.stack([head(v, weights[v]) for v in range(n_var)])
        return weights, heads, token_class.astype(jnp.int32)

    return jax.jit(make)


def make_family(cfg: dict, seed: int, index: int) -> Family:
    """Family ``index`` of the configuration, on the default device."""
    fn = _family_fn(cfg["vocab_size"], cfg[cfg["width_key"]],
                    cfg["classes"], cfg["variants_per_family"],
                    cfg["touched_row_blocks"], cfg["block_shape"][0],
                    cfg["head_doc_len"], float(cfg["delta_std"]))
    weights, heads, token_class = fn(seed_key(family_seed(seed, index)))
    return Family(cfg["families"][index], variant_names(cfg)[index],
                  weights, heads, np.asarray(token_class))


def store_config(cfg: dict):
    """The program's ``StoreConfig`` for the configuration: its blocks,
    pages, packing and fixed LSH parameters (no validation pass)."""
    from repro.core import DedupConfig, LSHConfig, StoreConfig
    lsh = cfg["lsh"]
    return StoreConfig(
        dedup=DedupConfig(
            block_shape=tuple(cfg["block_shape"]),
            lsh=LSHConfig(num_bands=lsh["num_bands"],
                          rows_per_band=lsh["rows_per_band"],
                          r=float(lsh["r"]),
                          collision_threshold=lsh["collision_threshold"]),
            validate=False),
        blocks_per_page=cfg["blocks_per_page"],
        pack_strategy=cfg["pack_strategy"])


def build_fleet(cfg: dict, seed: int, url: str):
    """Register every family's variants through ``DedupDB`` (Alg. 1) and
    commit the store to ``url``.  Returns ``(heads, token_class)``: per
    model, its float32 head ``[d, classes]`` and its family's
    token-class table ``[vocab]``."""
    from repro.db import DedupDB
    db = DedupDB.open(url, store_config(cfg))
    heads: Dict[str, np.ndarray] = {}
    token_class: Dict[str, np.ndarray] = {}
    for i in range(len(cfg["families"])):
        fam = make_family(cfg, seed, i)
        weights = np.asarray(fam.weights)
        fam_heads = np.asarray(fam.heads)
        for k, name in enumerate(fam.variants):
            db.register(name, {"embedding": weights[k]})
            heads[name] = fam_heads[k].copy()
            token_class[name] = fam.token_class
        del fam, weights
    db.commit()
    db.close()
    return heads, token_class
