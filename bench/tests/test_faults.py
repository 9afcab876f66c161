"""A run with its timed path broken underneath has to come out not correct.

Each case drives the whole harness (``run.run``) on the CPU at a small
size, past its look for a chip, with a fault planted in the served path
after the engine is built: every later request goes through it.  The
faults a served embedding cell can have:

  * a token's row altered where it is produced (the gather);
  * half of each document's tokens left out, the mean taken over the
    rest;
  * a request answered from another variant's rows (a wrong block map).

A training step that returns its state unchanged and an exchange between
chips left out do not exist in these one-chip serving cells.  One more
case compiles a new program in every batch, as a shape the warm-up
missed would: the run is not correct either (``window_compiles``).

    python -m pytest bench/tests/test_faults.py
"""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run  # noqa: E402

CELLS = ("ds7b-embed.hot", "mamba2-embed.bulk")


@pytest.fixture
def small(monkeypatch):
    """The cells at a size a test run holds: vocab 1024, width 512, one
    touched row block per variant; the configured limit unchanged."""
    load = run.load_cell

    def load_small(name):
        spec, cell, cfg, mix = load(name)
        cfg["vocab_size"], cfg[cfg["width_key"]] = 1024, 512
        cfg["touched_row_blocks"] = 1
        mix["pregenerate"] = min(mix["pregenerate"], 256)
        return spec, cell, cfg, mix

    monkeypatch.setattr(run, "load_cell", load_small)
    return load_small


def _gather_fault(engine, mutate):
    server = engine.server
    gather = server.device_gather_rows

    def broken(model, tensor, rows, *a, **kw):
        out = gather(model, tensor, rows, *a, **kw)
        if out is None:
            return out
        return mutate(model, np.array(out, copy=True), len(rows))

    server.device_gather_rows = broken


def alter_token(engine):
    def mutate(model, out, n):
        out[0, 0] += 0.5
        return out
    _gather_fault(engine, mutate)


def half_tokens(doc_len):
    def fault(engine):
        def mutate(model, out, n):
            docs = out[:n].reshape(-1, doc_len, out.shape[-1])
            half = doc_len // 2
            docs[:, half:2 * half] = docs[:, :half]
            out[:n] = docs.reshape(n, -1)
            return out
        _gather_fault(engine, mutate)
    return fault


def other_variant(engine):
    server = engine.server
    gather = server.device_gather_rows
    models = sorted(engine.heads)

    def broken(model, tensor, rows, *a, **kw):
        kw.pop("pages", None)
        sibling = models[(models.index(model) + 1) % len(models)]
        server.access_pages_grouped(
            sibling, server.embedding_rows_pages(sibling, tensor,
                                                 np.unique(rows)))
        return gather(sibling, tensor, rows, *a, **kw)

    server.device_gather_rows = broken


def compile_in_window(engine):
    """Each batch compiles a program of a shape never seen before, as a
    gather whose shape the warm-up missed would."""
    import jax
    import jax.numpy as jnp
    server = engine.server
    gather = server.device_gather_rows
    calls = [0]

    def compiling(*a, **kw):
        calls[0] += 1
        jax.jit(lambda x: x + 1)(jnp.zeros(calls[0])).block_until_ready()
        return gather(*a, **kw)

    server.device_gather_rows = compiling


def _run(cell, fault=None, seed=987654321012):
    return run.run(["--workload", cell, "--seed", str(seed),
                    "--seconds", "1", "--trace", "0"],
                   require_tpu=False, fault=fault)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(small, cell):
    out = _run(cell)
    assert out["correct"] is True
    assert out["checks"]["logit_err"]["value"] \
        <= out["checks"]["logit_err"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["alter_token", "half_tokens",
                                   "other_variant", "compile_in_window"])
def test_broken_path_is_not_correct(small, cell, fault):
    doc_len = small(cell)[3]["doc_len"]
    plant, check = {"alter_token": (alter_token, "logit_err"),
                    "half_tokens": (half_tokens(doc_len), "logit_err"),
                    "other_variant": (other_variant, "logit_err"),
                    "compile_in_window": (compile_in_window,
                                          "window_compiles")}[fault]
    out = _run(cell, plant)
    assert out["correct"] is False
    assert out["checks"][check]["value"] > out["checks"][check]["limit"]

