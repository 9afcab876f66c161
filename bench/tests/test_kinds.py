"""The kind contract: ``run.py`` drives an engine it knows only by its kind.

Each case drives the whole harness (``run.run``) on the CPU at a small
size, past its look for a chip:

  * a test-only kind (``fixtures/lm_kind.py``) serving two variants of a
    tiny dense LM through ``DedupDB.serve_lm`` / ``LMServingEngine``: the
    run is correct, and not correct with a served token or a served
    logit altered where the kind reads them; it reports only the metrics
    its cell names;
  * the embedding kind with its slab sharded over two shards (``shards``
    in the mix), whole and at half the fleet a shard: correct.

    python -m pytest bench/tests/test_kinds.py
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
FIXTURES = os.path.join(HERE, "fixtures")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run  # noqa: E402

SEED = 3_000_000_019


def _fixture_kind():
    spec = importlib.util.spec_from_file_location(
        "lm_fixture_kind", os.path.join(FIXTURES, "lm_kind.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def lm(monkeypatch):
    """``run.py`` pointed at the fixture kind and its cell."""
    with open(os.path.join(FIXTURES, "lm_cell.json")) as f:
        fx = json.load(f)
    kind = _fixture_kind()
    find = run.kind_module

    def load_cell(name):
        assert name == fx["cell"]["name"]
        return (json.loads(json.dumps(fx["spec"])), dict(fx["cell"]),
                dict(fx["config"]), dict(fx["mix"]))

    monkeypatch.setattr(run, "load_cell", load_cell)
    monkeypatch.setattr(run, "kind_module", lambda name: kind
                        if name == fx["config"]["kind"] else find(name))
    return fx, kind


def _run(cell, trace=0):
    return run.run(["--workload", cell, "--seed", str(SEED),
                    "--seconds", "1", "--trace", str(trace)],
                   require_tpu=False)


def test_lm_kind_is_correct(lm):
    fx, _ = lm
    out = _run(fx["cell"]["name"])
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    gap = out["checks"]["token_gap"]
    assert gap["value"] <= gap["limit"]
    assert out["checks"]["logit_err"]["value"] \
        <= out["checks"]["logit_err"]["limit"]
    assert list(out["checks"]) == ["token_gap", "logit_err",
                                   "fallback_batches", "window_compiles"]
    # only the metrics the cell names: docs_per_s is another cell's
    named = [m["name"] for m in fx["spec"]["end_to_end"]
             if "workloads" not in m
             or fx["cell"]["name"] in m["workloads"]]
    assert sorted(out["metrics"]) == sorted(named)
    assert "docs_per_s" not in out["metrics"]
    assert out["metrics"]["store_mb_per_variant"]["value"] > 0


@pytest.mark.parametrize("what,check", [("tokens", "token_gap"),
                                        ("logits", "logit_err")])
def test_lm_kind_altered_answer_is_not_correct(lm, monkeypatch, what,
                                               check):
    fx, kind = lm
    answer = kind.answer

    def altered(engine):
        tokens, logits = (np.array(a, copy=True) for a in answer(engine))
        if what == "tokens":
            tokens[0, -1] = (tokens[0, -1] + 1) % logits.shape[-1]
        else:
            logits[0, -1, 0] += 1.0
        return tokens, logits

    monkeypatch.setattr(kind, "answer", altered)
    out = _run(fx["cell"]["name"])
    assert out["correct"] is False
    assert out["checks"][check]["value"] > out["checks"][check]["limit"]


@pytest.fixture
def sharded(monkeypatch):
    """``ds7b-embed.hot`` at a size a test run holds (as in
    ``test_faults.py``), its slab over two shards."""
    load = run.load_cell

    def load_small(name, slab):
        spec, cell, cfg, mix = load(name)
        cfg["vocab_size"], cfg[cfg["width_key"]] = 1024, 512
        cfg["touched_row_blocks"] = 1
        mix["pregenerate"] = min(mix["pregenerate"], 256)
        mix["shards"] = 2
        mix["slab"] = slab
        return spec, cell, cfg, mix

    def use(slab):
        monkeypatch.setattr(run, "load_cell",
                            lambda name: load_small(name, slab))
    return use


@pytest.mark.parametrize("slab", [{"kind": "fleet"},
                                  {"kind": "fleet_frac", "frac": 0.5}])
def test_sharded_embedding_is_correct(sharded, slab, capsys):
    sharded(slab)
    out = _run("ds7b-embed.hot")
    assert out["correct"] is True
    assert out["checks"]["logit_err"]["value"] \
        <= out["checks"]["logit_err"]["limit"]
    assert out["metrics"]["docs_per_s"]["value"] > 0
    # both shards' slabs serve: each holds the mix's share of the fleet
    setup = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[setup]")][-1]
    fields = dict(kv.split("=", 1) for kv in setup.split()[1:])
    store_pages = int(fields["store_pages"])
    per_shard = store_pages if slab["kind"] == "fleet" \
        else round(slab["frac"] * store_pages)
    assert int(fields["slab_pages"]) == 2 * per_shard
