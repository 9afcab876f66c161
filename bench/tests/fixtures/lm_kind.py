"""A test-only kind: two variants of a tiny dense LM, served through
``DedupDB.serve_lm`` / ``LMServingEngine`` on the CPU.

It shows that the kind contract (``bench/kinds/__init__.py``) holds an
LM engine with no change to ``run.py`` or ``loop.py``.  The model is the
program's reduced ``deepseek-7b`` (two layers, d=64, vocab 256); the
variants share a base and differ in whole tensors (``touched``), so the
store shares every other block.  A request is ``[prompts, prompt_len]``
token ids for a model drawn from the seed, decoded ``steps`` tokens
greedily; its answer is ``engine.last_tokens`` with the logits the
served calls returned for them.  The check runs ``api.prefill`` /
``api.decode`` directly on the variant's own dense weights (kept from
``build``), feeding the served tokens back, and reads at each position,
over the spread of the reference's logits there, how far the served
token's logit lies below the best one (``token_gap``) and how far the
served logits lie from the reference's (``logit_err``).

The served calls are the model's ``prefill`` and ``decode`` compiled
once each: ``LMServingEngine`` calls them as it is given them, and the
model's own are eager, so their layer scan lowers at every call and a
window of them would not be correct (``window_compiles``).
``LMServingEngine`` counts a device batch at each model switch, not at
each batch, so ``failed_batches`` counts its host fallbacks and
degraded switches.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple

import numpy as np

import traffic
from loop import ClosedLoop


class Request(NamedTuple):
    index: int
    model: str
    prompts: np.ndarray        # [prompts_per_request, prompt_len] int32
    steps: int


class State(NamedTuple):
    api: object
    tensors: Dict[str, Dict[str, np.ndarray]]   # model -> name -> 2-D
    shapes: Dict[str, tuple]
    names: List[str]                            # leaf names, tree order
    treedef: object


def _key(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def _api(cfg: dict):
    from repro.configs import get_config, reduced
    from repro.models import build as build_model
    return build_model(reduced(get_config(cfg["arch"])))


def _params(state: State, tensors: Dict[str, np.ndarray]):
    """The parameter tree from a variant's stored 2-D tensors."""
    import jax
    import jax.numpy as jnp
    leaves = [jnp.asarray(np.asarray(tensors[n]).reshape(state.shapes[n]),
                          jnp.float32) for n in state.names]
    return jax.tree_util.tree_unflatten(state.treedef, leaves)


def build(cfg: dict, seed: int, url: str) -> State:
    import jax
    from repro.db import DedupDB
    from fleet import seed_key, store_config
    api = _api(cfg)
    key = seed_key(seed)

    def make(key):
        base = api.init(key)
        flat, _ = jax.tree_util.tree_flatten_with_path(base)
        out = []
        for v in range(len(cfg["variants"])):
            leaves = {}
            for i, (path, leaf) in enumerate(flat):
                name = _key(path)
                if v and name in cfg["touched"]:
                    leaf = leaf + cfg["delta_std"] * jax.random.normal(
                        jax.random.fold_in(key, 1000 * v + i), leaf.shape)
                leaves[name] = leaf
            out.append(leaves)
        return out

    variants = jax.jit(make)(key)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(api.init, key))
    names = [_key(p) for p, _ in flat]
    shapes = {_key(p): tuple(leaf.shape) for p, leaf in flat}
    tensors = {}
    db = DedupDB.open(url, store_config(cfg))
    for model, leaves in zip(cfg["variants"], variants):
        ts = {n: np.asarray(leaves[n], np.float32) for n in names}
        ts = {n: t.reshape(t.shape[0], -1) if t.ndim > 2 else t
              for n, t in ts.items()}
        db.register(model, ts)
        tensors[model] = ts
    db.commit()
    db.close()
    return State(api, tensors, shapes, names, treedef)


def _served_api(api, kept: list):
    """``api`` with ``prefill`` and ``decode`` compiled once each, keeping
    the logits every call returns in ``kept``."""
    import dataclasses
    import jax
    prefill = jax.jit(api.prefill, static_argnums=2)
    decode = jax.jit(api.decode)

    def kept_prefill(params, batch, max_len):
        logits, cache = prefill(params, batch, max_len)
        kept.append(logits)
        return logits, cache

    def kept_decode(params, cache, tokens):
        logits, cache = decode(params, cache, tokens)
        kept.append(logits)
        return logits, cache

    return dataclasses.replace(api, prefill=kept_prefill,
                               decode=kept_decode)


def open_engine(url: str, cfg: dict, mix: dict, state: State, devices):
    from repro.db import DedupDB
    db = DedupDB.open(url)
    rebuild = functools.partial(_params, state)
    kept = []
    api = _served_api(state.api, kept)
    models = cfg["variants"]
    engine = db.serve_lm({m: api for m in models},
                         {m: {"rebuild": rebuild} for m in models},
                         capacity_pages=db.store.num_pages(),
                         scheduler="round_robin", overlap=True,
                         compute_backend="device")
    engine.kept_logits = kept
    return db, engine


def _requests(cfg: dict, mix: dict, vocab: int, seed: int, n: int,
              stream: int, drawn: bool):
    """``n`` requests; their models drawn from the seed, or in turn
    (``drawn`` false: the warm-up, which covers every model)."""
    rng = traffic.seed_rng(seed, stream)
    models = cfg["variants"]
    picks = rng.integers(0, len(models), n) if drawn \
        else np.arange(n) % len(models)
    return [Request(i, models[int(picks[i])],
                    rng.integers(0, vocab, (mix["prompts_per_request"],
                                            mix["prompt_len"]),
                                 dtype=np.int32), mix["steps"])
            for i in range(n)]


def requests(cfg: dict, mix: dict, state: State, seed: int):
    vocab = state.api.cfg.vocab
    return (_requests(cfg, mix, vocab, seed, mix["pregenerate"],
                      traffic.WINDOW, True),
            _requests(cfg, mix, vocab, seed, mix["warmup_requests"],
                      traffic.WARMUP, False))


def warm(engine, cfg: dict, mix: dict, warmup) -> None:
    store = engine.server.store
    for m in cfg["variants"]:
        engine.server.access_pages_grouped(m, store.model_pages(m))
    store.fault_all()
    ClosedLoop(engine, warmup, mix["callers"], send, answer).serve_count(
        len(warmup))


def send(engine, req: Request) -> None:
    engine.submit(req.model, req.prompts, req.steps)


def answer(engine):
    """The batch's tokens ``[prompts, steps]`` and the logits the served
    calls returned for them ``[prompts, steps, vocab]``."""
    kept = engine.kept_logits
    logits = np.stack([np.asarray(lg, np.float64)[:, -1, :] for lg in kept],
                      axis=1)
    kept.clear()
    return engine.last_tokens, logits


def spans(engine, db):
    return None


def failed_batches(counters: dict) -> int:
    return counters["dense_fallbacks"] + counters["degraded_batches"]


def gaps(state: State, req: Request, served) -> tuple:
    """``(token_gap, logit_err)`` of one request: over its positions, the
    widest gap between the reference's best logit and the served token's,
    and the widest distance of a served logit from the reference's, each
    over that position's spread of the reference's logits."""
    import jax.numpy as jnp
    tokens, logits = (np.asarray(a) for a in served)
    shape = (len(req.prompts), req.steps)
    if tokens.shape != shape or logits.shape[:2] != shape:
        return float("inf"), float("inf")
    params = _params(state, state.tensors[req.model])
    ref, cache = state.api.prefill(
        params, {"tokens": jnp.asarray(req.prompts)},
        req.prompts.shape[1] + req.steps)
    token_gap = logit_err = 0.0
    for i in range(req.steps):
        if i:
            ref, cache = state.api.decode(
                params, cache, jnp.asarray(tokens[:, i - 1:i]))
        lg = np.asarray(ref, np.float64)[:, -1, :]
        got = np.take_along_axis(lg, tokens[:, i:i + 1], axis=1)[:, 0]
        spread = np.maximum(lg.max(axis=1) - lg.min(axis=1), 1e-30)
        token_gap = max(token_gap,
                        float(((lg.max(axis=1) - got) / spread).max()))
        logit_err = max(logit_err, float(
            (np.abs(logits[:, i, :] - lg).max(axis=1) / spread).max()))
    return token_gap, logit_err


def check(cfg: dict, seed: int, picked, state: State) -> dict:
    read = [gaps(state, d.request, d.answer) for d in picked]
    return {name: {"value": max((r[i] for r in read), default=0.0),
                   "limit": cfg["checks"][name]["limit"]}
            for i, name in enumerate(("token_gap", "logit_err"))}


def fields(cfg: dict, mix: dict, state: State, done, spans) -> dict:
    return {"variants": len(cfg["variants"])}
