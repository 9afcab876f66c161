"""Device-resident page pool: the HBM tier of the paper's buffer pool.

The paper pages deduplicated blocks between disk and DRAM; on TPU the
same two tiers are host DRAM (the ModelStore's distinct-block arrays)
and HBM (DESIGN.md §2).  :class:`DevicePagePool` is the HBM side:

  * a **fixed preallocated slab** ``[capacity_pages, page_rows, 128]``
    living on the accelerator: each page's ``blocks_per_page`` blocks
    laid end to end as 128-lane rows (see ``kernels/dedup_embedding``).
    A ``[..., 64, 64]`` float32 slab would be padded to 128 lanes on
    the TPU (twice its bytes) and could not be copied by DMA row by row;
    lane rows are dense.  Page loads are real ``jax.device_put`` +
    scatter transfers, not numpy copies;
  * a **physical→slot remap**: :meth:`remap` rewrites a
    ``ModelStore.virtual_tensor`` flat block map (physical slot space,
    ``page * l + slot``) into slab-slot space (``slab_slot * l + slot``)
    with one vectorized lookup, cached per (packing, slab) generation;
  * **compute entry points** — :meth:`gather_rows`, :meth:`virtual_matmul`,
    :meth:`unblock` — that run the Pallas dedup kernels (or their jitted
    XLA equivalents) directly against the resident slab, so inference
    never densifies weights on the host.

The pool is driven by :class:`~repro.core.bufferpool.BufferPool` through
its ``on_load``/``on_evict`` callbacks: the policy simulator stays the
single source of truth for *which* pages are resident, and this class
keeps the invariant ``slab occupied slots == pool resident set``.

Kernel mode — how :meth:`gather_rows` / :meth:`virtual_matmul` execute:

  * ``"pallas"``: the Pallas dedup kernels (interpret mode on the CPU —
    the correctness path the equivalence tests exercise).
  * ``"xla"``: jitted XLA gathers, the same math lowered without Pallas
    (the right choice on GPU).
  * ``"host"``: numpy gathers against a *host mirror* of the slab.  Off
    accelerator the "HBM" tier physically lives in host DRAM, so the
    mirror — maintained page-for-page with the slab — is the honest
    fast path there: same slot remap, same residency invariant, zero
    per-batch weight densification; interpret-mode Pallas and eager XLA
    gathers are correctness tools, not performance paths, on CPU.
  * ``"auto"`` (default): Pallas on TPU, the host mirror on CPU, XLA on
    any other accelerator.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.blocks import BlockGrid
from ..core.store import ModelStore, VirtualTensor
from ..kernels import ops
from ..kernels.dedup_embedding import LANES, lane_geometry
from ..obs import get_tracer
from .transfer import TransferEngine

__all__ = ["DevicePagePool"]


# --------------------------------------------------------- jitted XLA paths --
@functools.partial(jax.jit, static_argnames=("block_shape", "width"))
def _gather_rows_xla(slab, bmap2d, rows, *, block_shape, width: int):
    """Row gather without densifying: exactly the requested block rows
    are gathered from the slab's lane-row view — the XLA lowering of
    what dedup_embedding does by DMA."""
    bh, bw = block_shape
    rows_per_block = lane_geometry(block_shape)[0]
    lanes = slab.reshape(-1, LANES)
    rb, off = rows // bh, rows % bh
    start = bmap2d[rb] * rows_per_block + (off * bw // LANES)[:, None]
    col = (off * bw % LANES)[:, None, None] + jnp.arange(bw)   # [n, 1, bw]
    out = lanes[start[:, :, None] + col // LANES, col % LANES]  # [n, gw, bw]
    return out.reshape(out.shape[0], -1)[:, :width]


@functools.partial(jax.jit, static_argnames=("grid",))
def _unblock_xla(slab, dev_map, *, grid: BlockGrid):
    """Reassemble a full tensor from resident slab blocks on device
    (the LM-serving load path: zero host-side materialization)."""
    bh, bw = grid.block_shape
    rows_per_block = lane_geometry((bh, bw))[0]
    gh, gw = grid.grid
    blocks = jnp.take(slab.reshape(-1, rows_per_block, LANES), dev_map,
                      axis=0)
    x2 = (blocks.reshape(gh, gw, bh, bw)
                .transpose(0, 2, 1, 3)
                .reshape(gh * bh, gw * bw))
    return x2[:grid.shape2d[0], :grid.shape2d[1]].reshape(grid.tensor_shape)


@functools.partial(jax.jit, static_argnames=("grid",))
def _matmul_xla(slab, bmap2d, x, *, grid: BlockGrid):
    W = _unblock_xla(slab, bmap2d.reshape(-1), grid=grid)
    W = W.reshape(grid.shape2d)
    return jnp.matmul(x[..., :grid.shape2d[0]], W,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def pallas_matmul_accepts(block_shape) -> bool:
    """Block shapes pallas-mode :meth:`DevicePagePool.virtual_matmul`
    compiles for on the TPU (DESIGN.md §3): ``bw == 128`` so the
    lane-row slab reads as ``[n, bh, 128]`` blocks without a copy, and
    ``bh`` a multiple of 128 so the kernel's x tile ``(bm, bh)`` is
    lane-aligned."""
    bh, bw = block_shape
    return bw == LANES and bh % LANES == 0


def _pad_pow2(n: int, floor: int = 8) -> int:
    p = floor
    while p < n:
        p <<= 1
    return p


class DevicePagePool:
    """Fixed-capacity HBM slab of deduplicated pages + slot remap."""

    def __init__(self, store: ModelStore, capacity_pages: int,
                 dtype=jnp.float32, kernel_mode: str = "auto",
                 device=None, stage_rows: int = 0):
        if kernel_mode not in ("auto", "pallas", "xla", "host"):
            raise ValueError(f"unknown kernel_mode {kernel_mode!r}")
        self.store = store
        bh, bw = store.cfg.dedup.block_shape
        self.block_shape = (bh, bw)
        self.blocks_per_page = store.cfg.blocks_per_page
        # lane rows per page in the slab's [rows, page_rows, 128] layout
        self.page_rows = self.blocks_per_page * lane_geometry(
            self.block_shape)[0]
        self.capacity = int(capacity_pages)
        # Borrow-staging tail (sharded serving): ``stage_rows`` extra
        # page rows allocated PAST the resident slots, written by
        # ShardedPagePool once per staging change.  Extended remaps
        # point borrowed pages at ``capacity + stage_idx``, so the
        # kernels read one stable buffer — no per-call slab concat.
        self.stage_rows = int(stage_rows)
        self.dtype = dtype
        self.kernel_mode = kernel_mode
        # Mesh placement: a sharded pool pins each shard's slab (and its
        # compute) to one device of the serving mesh; None = default.
        self.device = device
        rows = self.capacity + self.stage_rows
        # The preallocated HBM slab. jnp.zeros commits the allocation on
        # the default device up front; every load is an in-place-style
        # functional update of this one buffer.  In host mode the mirror
        # below is the tier's physical backing, so the device buffer is
        # never allocated at all.
        self.slab = None if self.mode() == "host" else self._put(jnp.zeros(
            (rows, self.page_rows, LANES), dtype))
        # Host mirror, kept page-for-page identical with the slab: the
        # "host" kernel mode computes from it, and off-accelerator it is
        # the physical backing of the tier anyway.
        self.host_slab = np.zeros(
            (rows, self.blocks_per_page, bh, bw), np.float32)
        self.slot_of: Dict[int, int] = {}        # physical page id -> slot
        self._free: List[int] = list(range(self.capacity - 1, -1, -1))
        # page id -> slot as an int64 array (-1 = absent), maintained O(1)
        # per load/evict so per-batch remaps are pure vectorized lookups
        self._page_to_slot = np.full(store.packing.num_pages, -1,
                                     dtype=np.int64)
        self.generation = 0                      # bumped on load/evict/flush
        self.loads = 0
        self.evicts = 0
        # (model, tensor) -> (pack_gen, slab_gen, dev_map np.int32,
        #                     complete: no -1 holes)
        self._remap_cache: Dict[Tuple[str, str],
                                Tuple[int, int, np.ndarray, bool]] = {}
        # Batched/overlapped host->HBM movement (DESIGN.md §6): the
        # buffer pool's on_load_group callback lands in load_group(),
        # which stages a group's pages in ONE stacked buffer, ships it
        # with one device_put and commits it with one scatter.
        self.transfer = TransferEngine(self)

    def _put(self, x):
        """Commit an array to this pool's device (identity when unpinned)."""
        return x if self.device is None else jax.device_put(x, self.device)

    def to_lanes(self, pages: np.ndarray) -> np.ndarray:
        """Host pages ``[k, l, bh, bw]`` -> the slab's ``[k, page_rows,
        128]`` layout (a free reshape of contiguous float32 pages)."""
        return pages.reshape(len(pages), self.page_rows, LANES)

    # ------------------------------------------------------ page movement --
    def load(self, pid: int) -> None:
        """BufferPool ``on_load``: transfer one page host->device into a
        free slab slot.  In host mode the mirror *is* the device tier
        (host DRAM), so the jnp slab is left untouched — pallas/xla modes
        do the real ``device_put`` + ``dynamic_update_slice`` transfer.

        ``store.page_array`` sources the page through the store's
        attached :class:`~repro.storage.PageBackend` when one is present
        (a store opened from SQLite / a directory / the object-store
        sim): slab faults reach all the way down to the storage tier,
        and the engines' grouped demand fetches prefault the batch's
        pages in one backend round trip first."""
        if pid in self.slot_of:
            return
        with get_tracer().span("page_load", kind="transfer",
                               pid=int(pid), pages=1):
            # fetch BEFORE taking a slot: a storage fault mid-fetch must
            # not leak a free slot (exception safety under fault injection)
            page = self.store.page_array(pid, dtype=np.float32)
            slot = self._free.pop()
            # time only the host->HBM leg: page_array may have faulted the
            # storage backend, which must never leak into the fitted
            # channel
            t0 = time.perf_counter()
            if self.mode() != "host":
                self.slab = jax.lax.dynamic_update_slice(
                    self.slab,
                    self._put(jnp.asarray(self.to_lanes(page[None]),
                                          self.dtype)),
                    (slot, 0, 0))
            self.host_slab[slot] = page
            self.slot_of[pid] = slot
            self._page_to_slot[pid] = slot
            self.generation += 1
            self.loads += 1
            self.transfer.record_single(time.perf_counter() - t0)

    def load_group(self, pids) -> None:
        """BufferPool ``on_load_group``: transfer a whole group of pages
        host->device as ONE staged stack + one scatter + one generation
        bump (vs. the per-page path's K round trips and K bumps).  Pages
        prestaged by the engine's double buffer commit from the already
        in-flight device bytes (see :class:`TransferEngine`)."""
        self.transfer.load_group(pids)

    def evict(self, pid: int) -> None:
        """BufferPool ``on_evict``: release the page's slot.  The slab
        bytes are left in place — a slot without a slot_of entry is
        unreachable through any remap, so no scrub is needed."""
        slot = self.slot_of.pop(pid, None)
        if slot is None:
            return
        self._free.append(slot)
        self._page_to_slot[pid] = -1
        self.generation += 1
        self.evicts += 1

    def flush(self) -> None:
        """Forget every resident page (store repacked: page ids renamed,
        and the page-id universe may have changed size)."""
        self.slot_of.clear()
        self._free = list(range(self.capacity - 1, -1, -1))
        self._page_to_slot = np.full(self.store.packing.num_pages, -1,
                                     dtype=np.int64)
        self._remap_cache.clear()
        self.transfer.drop_pending()             # staged bytes are stale too
        self.generation += 1

    # ----------------------------------------------------------- queries --
    def resident_pages(self) -> Set[int]:
        return set(self.slot_of)

    def occupied_slots(self) -> Set[int]:
        return set(self.slot_of.values())

    def slot_page(self, slot: int) -> np.ndarray:
        """Host copy of one slab slot as ``[l, bh, bw]`` (tests /
        debugging)."""
        if self.mode() == "host":
            return self.host_slab[slot].copy()
        return np.asarray(self.slab[slot]).reshape(self.host_slab.shape[1:])

    def platform(self) -> str:
        """Platform of the device this pool's slab lives on."""
        return self.device.platform if self.device is not None \
            else jax.default_backend()

    def mode(self) -> str:
        """Resolved compute mode: pallas | xla | host.  ``auto`` is the
        host mirror only on the CPU; on the TPU it is the Pallas kernels,
        on any other accelerator the XLA gathers."""
        if self.kernel_mode != "auto":
            return self.kernel_mode
        return {"tpu": "pallas", "cpu": "host"}.get(self.platform(), "xla")

    def use_pallas(self) -> bool:
        return self.mode() == "pallas"

    # ------------------------------------------------------------- remap --
    def remap(self, vt: VirtualTensor,
              key: Optional[Tuple[str, str]] = None,
              strict: bool = True) -> Optional[np.ndarray]:
        """Rewrite a virtual tensor's physical flat block map into slab
        slot space with one vectorized lookup (cached per packing + slab
        generation under ``key``).

        ``strict=True`` returns None when *any* of the tensor's pages is
        not resident (whole-tensor consumers: unblock / virtual_matmul).
        ``strict=False`` returns the map with ``-1`` holes for absent
        pages — a row-gather caller that has already faulted its batch's
        pages (and verified them via :meth:`pages_resident`) only touches
        resident entries, so partial residency still serves off the slab.
        """
        hit = self._remap_cache.get(key) if key is not None else None
        if hit is not None and hit[0] == self.store.pack_generation \
                and hit[1] == self.generation:
            dev_map, complete = hit[2], hit[3]
        else:
            l = self.blocks_per_page
            slots = self._page_to_slot[vt.block_map // l]
            holes = slots < 0
            dev_map = np.where(holes, -1,
                               slots * l + vt.block_map % l).astype(np.int32)
            complete = not holes.any()
            if key is not None:
                self._remap_cache[key] = (self.store.pack_generation,
                                          self.generation, dev_map, complete)
        if strict and not complete:
            return None
        return dev_map

    def pages_resident(self, pages) -> bool:
        return all(p in self.slot_of for p in pages)

    # ------------------------------------------------------------ compute --
    def gather_rows(self, dev_map: np.ndarray, grid: BlockGrid,
                    rows: np.ndarray, pad: bool = False):
        """Rows of the virtual 2-D tensor, gathered from the resident
        slab.  Pallas mode runs ``dedup_embedding`` per column stripe;
        xla mode one jitted gather; host mode a numpy fancy-index gather
        from the slab mirror (returns np.ndarray).

        Sharded serving's borrowed pages live in the slab's own staging
        TAIL (``stage_rows`` past ``capacity`` — see ``__init__``), so
        an extended remap needs no extra buffer here.

        For the jit modes ``rows`` is padded to a power-of-two bucket so
        caches stay warm across varying batch row counts; ``pad=True``
        returns the padded ``[bucket, width]`` array (rows past ``n`` are
        row-0 garbage) so *downstream* jits also see stable shapes —
        indices into the first ``n`` rows are unaffected.

        Spans: ``gather_prep`` holds the host work before the kernel
        (the hole check, id padding, and the ``upload`` of ids and block
        map); ``kernel`` holds the dispatch alone."""
        bh, bw = self.block_shape
        gh, gw = grid.grid
        width = grid.shape2d[1]
        tr = get_tracer()
        with tr.span("gather_prep", kind="kernel"):
            rows = np.asarray(rows)      # repro: allow-host (index array)
            n = len(rows)
            bmap2d = dev_map.reshape(gh, gw)
            # Partial remaps carry -1 holes; negative indexing would
            # silently wrap to the wrong slab bytes, so a touched hole (the
            # caller's page set failed to cover its rows) must surface as
            # None — the engines then take the host fallback instead of
            # serving garbage.
            if n and (bmap2d[np.unique(rows // bh)] < 0).any():
                return None
            mode = self.mode()
            if mode != "host":
                # Pad with a *requested* row, not row 0: under partial
                # residency row 0's block may be absent and must never be
                # touched.
                ids = np.full(_pad_pow2(max(n, 1)), rows[0] if n else 0,
                              np.int32)
                ids[:n] = rows
                with tr.span("upload", kind="transfer"):
                    ids_dev = self._put(jnp.asarray(ids))
                    bmap_dev = self._put(jnp.asarray(bmap2d))
        if mode == "host":
            l = self.blocks_per_page
            with tr.span("kernel", kind="kernel", op="gather_rows",
                         mode=mode, rows=n):
                slab = self.host_slab
                flat_rows = slab.reshape(slab.shape[0] * l * bh, bw)
                rb, off = rows // bh, rows % bh
                out = flat_rows[bmap2d[rb] * bh + off[:, None]]  # [n,gw,bw]
                return out.reshape(n, gw * bw)[:, :width]
        with tr.span("kernel", kind="kernel", op="gather_rows", mode=mode,
                     rows=n):
            if mode == "pallas":
                out = ops.dedup_embedding_striped(
                    ids_dev, self.slab, bmap_dev, self.block_shape,
                    width=width)
            else:
                out = _gather_rows_xla(self.slab, bmap_dev, ids_dev,
                                       block_shape=self.block_shape,
                                       width=width)
        return out if pad else out[:n]

    def virtual_matmul(self, dev_map: np.ndarray, grid: BlockGrid, x):
        """``x @ W_virtual`` with W never densified: dedup_matmul streams
        slab blocks through the scalar-prefetched block map (pallas);
        host mode runs the same k-loop blockwise in numpy against the
        slab mirror.  Pallas mode on the TPU raises for block shapes
        :func:`pallas_matmul_accepts` refuses; it never falls back."""
        bh, bw = self.block_shape
        gh, gw = grid.grid
        K, N = grid.shape2d
        bmap2d = dev_map.reshape(gh, gw)
        mode = self.mode()
        l = self.blocks_per_page
        if mode == "host":
            slab = self.host_slab
            blocks = slab.reshape(slab.shape[0] * l, bh, bw)
            # repro: allow-host — host-mode kernel: the mirror IS the tier
            x = np.asarray(x, dtype=np.float32)
            xp = x
            if x.shape[-1] != gh * bh:
                assert x.shape[-1] == K, (x.shape, K)
                xp = np.zeros(x.shape[:-1] + (gh * bh,), np.float32)
                xp[..., :K] = x
            y = np.zeros(x.shape[:-1] + (gw * bw,), np.float32)
            for j in range(gw):                  # the kernel's (j, k) loops
                acc = y[..., j * bw:(j + 1) * bw]
                for k in range(gh):
                    acc += xp[..., k * bh:(k + 1) * bh] \
                        @ blocks[bmap2d[k, j]]
            return y[..., :N]
        with get_tracer().span("kernel", kind="kernel",
                               op="virtual_matmul", mode=mode):
            if mode == "pallas":
                if not ops._interpret() \
                        and not pallas_matmul_accepts(self.block_shape):
                    raise ValueError(
                        f"pallas virtual_matmul does not compile for "
                        f"{self.block_shape} blocks on the TPU; it needs "
                        f"bw == {LANES} and bh a multiple of {LANES} "
                        f"(DESIGN.md §3)")
                pad = gh * bh - x.shape[-1]
                if pad:
                    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
                    x = jnp.pad(x, widths)
                bm = 8 if ops._interpret() else 128
                pool = self.slab.reshape(-1, bh, bw)
                y = ops.dedup_matmul(self._put(x), pool,
                                     self._put(jnp.asarray(bmap2d)), bm=bm)
                return y[..., :N]
            if x.shape[-1] != gh * bh:  # _matmul_xla slices x to K itself
                assert x.shape[-1] == K, (x.shape, K)
            return _matmul_xla(self.slab,
                               self._put(jnp.asarray(bmap2d)),
                               self._put(x), grid=grid)

    def unblock(self, dev_map: np.ndarray, grid: BlockGrid):
        """Full tensor reassembled from resident slab blocks (the LM
        model-switch path; np from the mirror in host mode, on-device
        otherwise)."""
        l = self.blocks_per_page
        bh, bw = self.block_shape
        mode = self.mode()
        with get_tracer().span("kernel", kind="kernel", op="unblock",
                               mode=mode):
            if mode == "host":
                from ..core.blocks import unblock_tensor
                slab = self.host_slab
                blocks = slab.reshape(slab.shape[0] * l, bh, bw)[dev_map]
                return unblock_tensor(blocks, grid)
            return _unblock_xla(self.slab,
                                self._put(jnp.asarray(dev_map)), grid=grid)
