"""Batched, overlapped host->HBM page transfers (DESIGN.md §6).

The per-page miss path pays K serialized host->HBM round trips for a
batch with K misses: one ``jax.device_put`` plus one slab-sized
``dynamic_update_slice`` each (``DevicePagePool.load``).  The
:class:`TransferEngine` is the grouped alternative the buffer pool's
``on_load_group`` callback drives:

  * **coalesce** — a group's pages are assembled into ONE stacked host
    staging buffer (``ModelStore.page_stack``: a single grouped backend
    fault plus one vectorized gather, never K ``page_array`` calls);
  * **one transfer** — the stack ships with a single ``device_put`` and
    commits with a single scatter (``slab.at[slots].set``), so the slab
    is rewritten once per group, not once per page;
  * **one generation bump** — downstream remap caches are invalidated
    once per group instead of K times;
  * **double buffering** — :meth:`stage` lets the serving engine issue
    the *next* batch's transfer while the current batch computes.  JAX
    dispatch is asynchronous, so the ``device_put`` overlaps the
    in-flight compute; when the group is later committed the bytes are
    already device-side and the commit is just the scatter.  Staged-
    ahead bytes are counted as *overlapped* (``ServeStats.
    overlap_fraction``).

Every movement — grouped or the pool's per-page fallback — is recorded
as an issue-side ``(pages, bytes, seconds)`` sample for observability;
:meth:`storage_model` fits ``seconds = seek + bytes / bandwidth`` over
a *blocking* :meth:`measure` sweep (serving samples time async
dispatch, not the transfer), so the host<->HBM channel of the virtual
clock is charged at the measured group-transfer bandwidth of this
machine instead of a preset per-page guess.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import get_tracer

__all__ = ["TransferStats", "PendingGroup", "TransferEngine",
           "fit_channel"]

#: samples kept for the bandwidth fit (serving runs are unbounded)
_MAX_RECORDS = 512


def _bucket_pad(*arrs: np.ndarray):
    """Pad index arrays (all the same length) to the next power of two
    by repeating their first element — duplicate gathers/writes of
    identical rows are harmless — so varying group sizes reuse a few
    compiled gather/scatter shapes instead of recompiling per size."""
    n = len(arrs[0])
    bucket = 1
    while bucket < n:
        bucket <<= 1
    if bucket == n:
        return arrs if len(arrs) > 1 else arrs[0]
    out = tuple(np.concatenate([a, np.full(bucket - n, a[0], a.dtype)])
                for a in arrs)
    return out if len(out) > 1 else out[0]


@dataclasses.dataclass
class TransferStats:
    """Host->HBM movement counters for one TransferEngine."""
    groups: int = 0              # commit operations (a per-page load = 1)
    pages: int = 0               # pages moved host->HBM
    bytes: int = 0               # bytes moved host->HBM
    seconds: float = 0.0         # issue-side wall seconds (async dispatch)
    overlapped_bytes: int = 0    # bytes that were staged ahead of demand
    staged_groups: int = 0       # prestage() calls that issued a transfer
    records: List[Tuple[int, int, float]] = \
        dataclasses.field(default_factory=list)   # (pages, bytes, seconds)

    def record(self, pages: int, nbytes: int, seconds: float,
               overlapped_bytes: int = 0) -> None:
        self.groups += 1
        self.pages += pages
        self.bytes += nbytes
        self.seconds += seconds
        self.overlapped_bytes += overlapped_bytes
        if len(self.records) < _MAX_RECORDS:
            self.records.append((pages, nbytes, seconds))

    @property
    def overlap_fraction(self) -> float:
        return self.overlapped_bytes / self.bytes if self.bytes else 0.0


@dataclasses.dataclass
class PendingGroup:
    """A staged (not yet committed) transfer: host stack assembled, the
    device copy already issued (async) when the pool has a device slab."""
    index: Dict[int, int]            # pid -> row in the stack
    host: np.ndarray                 # [k, l, bh, bw] staging buffer
    dev: Optional[object]            # [k, page_rows, 128] device copy
                                     # (None in host mode)
    pack_generation: int


def fit_channel(records: Sequence[Tuple[int, int, float]]
                ) -> Tuple[float, float]:
    """Least-squares ``seconds = seek + bytes/bandwidth`` over measured
    group samples; returns ``(bandwidth B/s, seek seconds)`` clamped to
    sane ranges (degenerate sample sets fall back to mean throughput)."""
    recs = [(b, t) for _, b, t in records if t > 0 and b > 0]
    if not recs:
        return 20e9, 1e-6                      # dram-ish: nothing measured
    xs = np.array([b for b, _ in recs], np.float64)
    ys = np.array([t for _, t in recs], np.float64)
    if len(recs) >= 2 and np.ptp(xs) > 0:
        slope, seek = np.polyfit(xs, ys, 1)
        if slope <= 0:
            # flat (or noise-inverted) size axis: the channel is per-
            # OPERATION dominated — model it as pure seek, free bytes
            return 1e13, float(np.mean(ys))
        seek = max(seek, 0.0)
    else:
        slope, seek = float(np.mean(ys / xs)), 0.0
    bandwidth = float(np.clip(1.0 / max(slope, 1e-15), 1e6, 1e14))
    return bandwidth, float(max(seek, 0.0))


class TransferEngine:
    """Grouped page movement for one :class:`~repro.serving.device_pool.
    DevicePagePool`.  The pool owns residency bookkeeping state (slots,
    generation); this class owns how bytes get there."""

    def __init__(self, pool, max_pending: int = 2):
        self.pool = pool
        self.max_pending = max_pending
        self.stats = TransferStats()
        self._pending: "OrderedDict[frozenset, PendingGroup]" = OrderedDict()

    # ------------------------------------------------------------ helpers --
    @property
    def page_nbytes(self) -> int:
        bh, bw = self.pool.block_shape
        return self.pool.blocks_per_page * bh * bw \
            * np.dtype(np.float32).itemsize

    def _missing(self, pids) -> List[int]:
        seen, out = set(), []
        for p in pids:
            p = int(p)
            if p not in seen and p not in self.pool.slot_of:
                seen.add(p)
                out.append(p)
        return out

    # Callers (load_group / stage) own the channel charge; _stack
    # only assembles bytes.  # repro: allow-uncharged
    def _stack(self, pids: List[int]) -> np.ndarray:
        """One grouped backend fault + one vectorized gather."""
        return self.pool.store.page_stack(pids, dtype=np.float32)

    def _to_device(self, stack: np.ndarray):
        """Ship a host page stack ``[k, l, bh, bw]`` in the slab's
        lane-row layout ``[k, page_rows, 128]``."""
        import jax.numpy as jnp
        return self.pool._put(jnp.asarray(self.pool.to_lanes(stack),
                                          self.pool.dtype))

    def _scatter(self, slab, slots: np.ndarray, staged):
        """One scatter committing ``staged`` rows into ``slots``, padded
        to a power-of-two bucket (``_bucket_pad``; callers that already
        padded pass pow2 inputs and this is a no-op)."""
        import jax.numpy as jnp
        padded = _bucket_pad(slots)
        if len(padded) > len(slots):
            staged = jnp.concatenate(
                [staged, jnp.broadcast_to(
                    staged[:1], (len(padded) - len(slots),)
                    + staged.shape[1:])], axis=0)
            slots = padded
        return slab.at[jnp.asarray(slots, jnp.int32)].set(staged)

    def drop_pending(self) -> None:
        self._pending.clear()

    def _fresh_pending(self) -> None:
        """Evict stale (repacked) and over-quota pending stages."""
        gen = self.pool.store.pack_generation
        for key in [k for k, pg in self._pending.items()
                    if pg.pack_generation != gen]:
            del self._pending[key]
        while len(self._pending) > self.max_pending:
            self._pending.popitem(last=False)

    # ------------------------------------------------------------ staging --
    def stage(self, pids) -> Optional[PendingGroup]:
        """Assemble ``pids``'s not-yet-resident pages into one staging
        stack and issue the (async) device copy.  The engines call this
        for the *next* batch right before computing the current one, so
        the copy rides under compute — JAX dispatch returns immediately.
        Commit happens later, when the buffer pool actually admits the
        pages (:meth:`load_group`)."""
        self.pool.store.packing                  # settle before gen read
        self._fresh_pending()
        missing = self._missing(pids)
        if not missing:
            return None
        key = frozenset(missing)
        hit = self._pending.get(key)
        if hit is not None:
            return hit
        for staged in self._pending.values():    # already covered by one?
            if key <= staged.index.keys():
                return staged
        with get_tracer().span("stage", kind="transfer",
                               pages=len(missing),
                               bytes=len(missing) * self.page_nbytes):
            stack = self._stack(missing)
            dev = None if self.pool.mode() == "host" \
                else self._to_device(stack)
        pg = PendingGroup({p: i for i, p in enumerate(missing)}, stack, dev,
                          self.pool.store.pack_generation)
        self._pending[key] = pg
        while len(self._pending) > self.max_pending:
            self._pending.popitem(last=False)
        self.stats.staged_groups += 1
        return pg

    # ------------------------------------------------------------- commit --
    def _full_cover(self, missing: List[int]) -> Optional[PendingGroup]:
        """A pending group whose staged bytes cover the WHOLE commit
        (the double-buffer hit).  Partial covers are not spliced — the
        splice would need shape-varying device gathers/concats that
        recompile per group; a clean restage is cheaper and rarer."""
        key = set(missing)
        for pg in self._pending.values():
            if key <= pg.index.keys():
                return pg
        return None

    def load_group(self, pids) -> int:
        """Commit a group: one scatter into the slab, one host-mirror
        write, one generation bump.  A group fully staged by a previous
        :meth:`stage` commits from the already in-flight device bytes
        (the overlapped path, counted in ``overlapped_bytes``); anything
        else is staged now.  Returns pages loaded."""
        self._fresh_pending()
        missing = self._missing(pids)
        if not missing:
            return 0
        if len(missing) > len(self.pool._free):
            raise RuntimeError(
                f"group of {len(missing)} pages exceeds the slab's "
                f"{len(self.pool._free)} free slots")
        with get_tracer().span("load_group", kind="transfer",
                               pages=len(missing),
                               bytes=len(missing) * self.page_nbytes) as sp:
            pg = self._full_cover(missing)
            overlapped = 0
            if pg is not None:
                rows = np.asarray([pg.index[p] for p in missing],  # repro: allow-host
                                  dtype=np.int64)
                host_stack = pg.host[rows]
                # staged ahead of demand: in device modes the bytes are
                # already in flight to HBM; in host mode the staging stack
                # (the grouped store gather) was assembled under compute
                overlapped = len(missing) * self.page_nbytes
                for key in [k for k, v in self._pending.items() if v is pg]:
                    del self._pending[key]       # consumed
            else:
                rows = None
                host_stack = self._stack(missing)
            # Time only the host->HBM leg (mirror write + device_put +
            # scatter): _stack() above may fault the STORAGE backend, and
            # a channel fitted over storage seconds would double-charge
            # misses under charge_transfer.
            t0 = time.perf_counter()
            slots = np.asarray([self.pool._free.pop() for _ in missing],  # repro: allow-host
                               dtype=np.int64)
            # Exception safety: slots are popped, but residency maps are
            # not yet touched.  If the device leg fails, every popped slot
            # goes back to the free list and the generation is NOT bumped
            # — the pool looks exactly as before the call (no half-mapped
            # slots; slab bytes in an unmapped slot are unreachable by any
            # remap).
            try:
                self.pool.host_slab[slots] = host_stack
                if self.pool.mode() != "host":
                    if pg is not None and pg.dev is not None:
                        # reuse the staged device bytes: bucket-pad the
                        # gather and the scatter to the SAME pow2 shape
                        # (repeat index 0; duplicate writes of identical
                        # rows are harmless), so varying group sizes hit a
                        # few compiled shapes
                        rows_p, slots_p = _bucket_pad(rows, slots)
                        import jax.numpy as jnp
                        staged = pg.dev[jnp.asarray(rows_p, jnp.int32)]
                        self.pool.slab = self._scatter(self.pool.slab,
                                                       slots_p, staged)
                    else:
                        self.pool.slab = self._scatter(
                            self.pool.slab, slots,
                            self._to_device(host_stack))
            except BaseException:
                self.pool._free.extend(int(s) for s in slots)
                raise

            for pid, slot in zip(missing, slots):
                self.pool.slot_of[pid] = int(slot)
                self.pool._page_to_slot[pid] = int(slot)
            self.pool.generation += 1            # ONCE per group
            self.pool.loads += len(missing)
            self.stats.record(len(missing),
                              len(missing) * self.page_nbytes,
                              time.perf_counter() - t0,
                              overlapped_bytes=overlapped)
            sp.set(overlapped_bytes=overlapped)
        return len(missing)

    def record_single(self, seconds: float) -> None:
        """Per-page fallback accounting (``DevicePagePool.load``): the
        same stats stream, a group of one."""
        self.stats.record(1, self.page_nbytes, seconds)

    # -------------------------------------------------------- calibration --
    def measure(self, group_sizes: Sequence[int] = (1, 2, 4, 8),
                reps: int = 3) -> List[Tuple[int, int, float]]:
        """Blocking bandwidth sweep: time a size-n staged transfer +
        scatter end to end (``block_until_ready``) for each group size,
        without touching residency (the scatter result is discarded).
        Returns ``(pages, bytes, best seconds)`` samples."""
        bh, bw = self.pool.block_shape
        l = self.pool.blocks_per_page
        out: List[Tuple[int, int, float]] = []
        rng = np.random.default_rng(0)
        for n in group_sizes:
            n = int(min(n, max(1, self.pool.capacity)))
            src = rng.standard_normal((n, l, bh, bw)).astype(np.float32)
            slots = np.arange(n, dtype=np.int64)
            best = float("inf")
            # one untimed warmup per size so compile/allocator effects
            # never pollute the fit
            for rep in range(max(1, reps) + 1):
                t0 = time.perf_counter()
                if self.pool.mode() == "host":
                    # host tier: the "transfer" is a mirror memcpy
                    scratch = np.empty_like(src)
                    scratch[:] = src
                else:
                    dev = self._to_device(src)
                    res = self._scatter(self.pool.slab, slots, dev)
                    res.block_until_ready()
                if rep:
                    best = min(best, time.perf_counter() - t0)
            out.append((n, n * self.page_nbytes, best))
        return out

    def storage_model(self, group_sizes: Sequence[int] = (1, 2, 4, 8),
                      reps: int = 3, **kw):
        """A :class:`~repro.serving.engine.StorageModel` of the host<->HBM
        channel, fitted from a BLOCKING :meth:`measure` sweep — the
        calibrated replacement for preset per-page charges.  The serving
        ``stats.records`` are deliberately NOT used: serving timings are
        issue-side (JAX dispatch is asynchronous), so on an accelerator
        they measure dispatch latency, not the transfer."""
        bandwidth, seek = fit_channel(self.measure(group_sizes, reps))
        from .engine import StorageModel
        kw.setdefault("channel", "hbm")
        return StorageModel(kind=f"measured:{self.pool.mode()}",
                            bandwidth=bandwidth, seek=seek, **kw)
