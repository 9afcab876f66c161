"""Kinds of served model: what ``bench/run.py`` asks of a configuration.

A configuration names its kind (``"kind"`` in its file; without it,
``embedding``), and ``run.kind_module`` imports ``bench/kinds/<kind>.py``.
``run.py`` times set-up, warm-up and the window, drives the closed loop,
counts compiles and staged stack sizes, reads memory and the
trace, and prints the result; everything that depends on what is served
and how it is checked is the kind's.  A kind is a module with these
functions (``state`` is whatever ``build`` returns; nothing else of it is
read by ``run.py``):

  build(cfg, seed, url) -> state
      Make the fleet's weights from the seed, register every variant
      through ``DedupDB`` and commit the store to ``url``.  ``state``
      holds what the requests and the check need (heads, token tables),
      made by the benchmark, never taken from the program.
  open_engine(url, cfg, mix, state, devices) -> (db, engine)
      Reopen the committed store with ``DedupDB.open`` and build the
      served engine on it.  ``devices`` are the chips the run found.  The
      engine has ``submit``/``run(max_batches=1)``, ``scheduler``,
      ``stats`` (``ServeStats``) and ``server`` (``pool``; ``device_pool``
      when it serves from the HBM slab).  A kind that needs a TPU kernel
      path raises here when ``devices`` are TPUs and it is not taken.
  requests(cfg, mix, state, seed) -> (window, warmup)
      The window's and the warm-up's requests, made from the seed; every
      seed gets the same amounts.  Each request has ``index`` and
      ``model``.
  warm(engine, cfg, mix, warmup)
      Bring the served path to its steady state with the warm-up
      requests, so that nothing compiles in the window.  (``run.py``
      compiles the gathers from staged page stacks afterwards.)
  send(engine, req)
      Submit one request (``loop.ClosedLoop`` calls it).
  answer(engine)
      The answer to the batch ``engine.run(max_batches=1)`` just served,
      on the host (``loop.ClosedLoop`` keeps it as ``Done.answer``).
  spans(engine, db) -> object or None
      With ``--trace 1``, install the kind's host spans (``bench.*``)
      before the window; ``None`` installs none.  ``run.py`` opens
      ``bench.window`` itself.
  failed_batches(counters) -> int
      How many of the window's batches the served path answered off
      its device path, from the change over the window of ``run.py``'s
      counters: the engine's ``ServeStats`` (``batches``,
      ``device_batches``, ``dense_fallbacks``, ``degraded_batches``) and
      its buffer pool's ``hits`` and ``misses``.  ``run.py`` reports it
      as ``failed`` and holds it to 0 (``fallback_batches``).  Engines
      count their device batches differently (the embedding engine each
      batch, the LM engine each model switch), so the kind reads them.
  check(cfg, seed, picked, state) -> {name: {"value", "limit"}}
      Run the plain reference on the sampled answered requests
      (``loop.Done``) once the program's state is freed, and return each
      number compared beside its limit.  ``run.py`` adds
      ``fallback_batches`` and ``window_compiles``; a run is correct when
      the sample is not empty and every value is at most its limit.
  fields(cfg, mix, state, done, spans) -> dict
      The kind's fields for the metric readers (``bench/metrics``), read
      from the answered requests before the program's state is freed.
      It gives ``variants`` (the models the store holds) and whatever
      its own metrics read (the embedding kind: ``docs``, ``width``,
      ``gather_ids``).  ``run.py`` gives ``cfg``, ``mix``, ``cell``,
      ``setup_s``, ``store_bytes``, ``window_s``, ``requests``,
      ``latencies_s``, ``counters`` and, traced, ``trace``, ``peaks``,
      ``lo`` and ``hi``.

Adding a kind adds a file here, a configuration that names it, and its
reference, mixes and metrics; it edits none.
"""
from __future__ import annotations


def device_pools(server) -> list:
    """The HBM page pools a server serves from: one ``DevicePagePool``,
    a sharded pool's per-shard pools, or none on the host backend."""
    pool = server.device_pool
    if pool is None:
        return []
    return list(getattr(pool, "pools", [pool]))
