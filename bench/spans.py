"""Host spans the traced run opens around calls into the program's layers.

Each wrapper replaces one bound method on one object with a function that
opens a ``jax.profiler.TraceAnnotation`` named ``bench.<layer>`` around
the original call, so the profiler's trace holds the host's spans on the
same clock as the device's operations.  The wrappers live here, not in
the program, and are installed only in a traced run.

Span names and the layer each stands for:

  bench.window          the measured window
  bench.submit          EmbeddingServingEngine.submit   (engine)
  bench.engine.run      EmbeddingServingEngine.run      (engine)
  bench.fetch           WeightServer.access_pages_grouped (page access)
  bench.storage_fetch   PageBackend.get_pages           (storage)
  bench.stage           TransferEngine.stage            (transfer)
  bench.transfer_commit TransferEngine.load_group       (transfer)
  bench.page_load       BufferPool.on_load (per page)   (transfer)
  bench.prestage        WeightServer.prestage           (transfer)
  bench.gather          WeightServer.device_gather_rows (kernel call)
  bench.head            engine._tok_logits              (pool + head)
"""
from __future__ import annotations

from typing import List

TRANSFER = ("bench.stage", "bench.transfer_commit", "bench.page_load")


def _wrap(obj, attr: str, name: str, note=None):
    from jax.profiler import TraceAnnotation
    fn = getattr(obj, attr)

    def wrapped(*a, **kw):
        if note is not None:
            note(*a, **kw)
        with TraceAnnotation(name):
            return fn(*a, **kw)

    setattr(obj, attr, wrapped)


class Spans:
    """Installs the wrappers on one engine; counts the ids gathered."""

    def __init__(self, engine, db):
        import repro.serving.engine as engine_mod
        from kinds import device_pools
        server = engine.server
        self.gather_ids: List[int] = []   # ids per gather call, in order
        _wrap(engine, "submit", "bench.submit")
        _wrap(engine, "run", "bench.engine.run")
        _wrap(server, "access_pages_grouped", "bench.fetch")
        _wrap(db.backend, "get_pages", "bench.storage_fetch")
        for pool in device_pools(server):
            _wrap(pool.transfer, "stage", "bench.stage")
            _wrap(pool.transfer, "load_group", "bench.transfer_commit")
        # a sharded pool's buffer pools are per shard
        for bp in getattr(server.device_pool, "buffer_pools", [server.pool]):
            if bp.on_load is not None:
                _wrap(bp, "on_load", "bench.page_load")
        _wrap(server, "prestage", "bench.prestage")
        _wrap(server, "device_gather_rows", "bench.gather",
              note=lambda model, tensor, rows, *a, **kw:
              self.gather_ids.append(len(rows)))
        _wrap(engine_mod, "_tok_logits", "bench.head")
