from collections import OrderedDict, defaultdict

import numpy as np
import pytest

from repro.core.bufferpool import POLICIES, BufferPool, PoolConfig, run_trace


def _pool(cap=4, policy="lru", sharers=None, locality=None, **kw):
    return BufferPool(PoolConfig(cap, policy, **kw),
                      page_sharers=sharers, page_locality=locality)


def test_lru_eviction_order():
    p = _pool(2, "lru")
    p.access("m", "a")
    p.access("m", "b")
    p.access("m", "a")           # refresh a
    p.access("m", "c")           # evicts b (least recent)
    assert "b" not in p.resident
    assert {"a", "c"} <= set(p.resident)


def test_mru_eviction_order():
    p = _pool(2, "mru")
    p.access("m", "a")
    p.access("m", "b")
    p.access("m", "c")           # evicts b (most recent resident)
    assert set(p.resident) == {"a", "c"}


def test_lfu_prefers_frequency():
    p = _pool(2, "lfu")
    for _ in range(3):
        p.access("m", "hot")
    p.access("m", "cold")
    p.access("m", "new")         # cold has lowest freq -> evicted
    assert "hot" in p.resident and "cold" not in p.resident


def test_hit_ratio_accounting():
    p = _pool(8)
    trace = [("m", i % 4) for i in range(40)]
    hr = run_trace(p, trace)
    assert p.hits == 36 and p.misses == 4
    assert hr == pytest.approx(0.9)


def test_eq2_shared_pages_survive():
    """Pages shared by more models get higher p_reuse (Eq. 2) -> kept."""
    sharers = {"shared": ["m1", "m2", "m3"], "p1": ["m1"],
               "p2": ["m2"], "p3": ["m3"]}
    locality = {k: "L" for k in sharers}      # one locality set
    p = _pool(2, "optimized_lru", sharers=sharers, locality=locality,
              horizon_t=8.0)
    rng = np.random.default_rng(0)
    models = ["m1", "m2", "m3"]
    # every request touches the shared page + the model's private page
    for i in range(60):
        m = models[int(rng.integers(0, 3))]
        p.access(m, "shared")
        p.access(m, f"p{m[1]}")
    assert "shared" in p.resident


def test_optimized_beats_lru_on_shared_trace():
    """The paper's claim (Fig. 14): Eq.-2-aware eviction improves hit ratio
    on multi-model traffic with shared pages."""
    def build(policy):
        sharers = {f"s{i}": ["m1", "m2", "m3", "m4"] for i in range(3)}
        sharers.update({f"q{m}{i}": [f"m{m}"] for m in range(1, 5)
                        for i in range(4)})
        locality = {k: ("S" if k.startswith("s") else f"P{k[1]}")
                    for k in sharers}
        return BufferPool(PoolConfig(6, policy, horizon_t=12.0),
                          page_sharers=sharers, page_locality=locality)

    def trace(seed=1, n=400):
        rng = np.random.default_rng(seed)
        out = []
        for _ in range(n):
            m = f"m{int(rng.integers(1, 5))}"
            for i in range(3):
                out.append((m, f"s{i}"))           # shared working set
            out.append((m, f"q{m[1]}{int(rng.integers(0, 4))}"))
        return out

    hr = {pol: run_trace(build(pol), trace())
          for pol in ("lru", "optimized_lru")}
    assert hr["optimized_lru"] > hr["lru"]


def test_callbacks_fire():
    loaded, evicted = [], []
    p = BufferPool(PoolConfig(1, "lru"), on_load=loaded.append,
                   on_evict=evicted.append)
    p.access("m", "a")
    p.access("m", "b")
    assert loaded == ["a", "b"] and evicted == ["a"]


def test_all_policies_run():
    trace = [("m%d" % (i % 3), i % 7) for i in range(100)]
    for pol in POLICIES:
        p = _pool(3, pol)
        hr = run_trace(p, trace)
        assert 0 <= hr <= 1
        assert len(p.resident) <= 3


def test_bad_policy_rejected():
    with pytest.raises(ValueError):
        PoolConfig(4, "clock")


# ------------------------------------------------- victim index vs scan --
class _ScanPool(BufferPool):
    """The scan-based victim choice the per-set index replaced: the
    evictable list, bucketed by locality set, and the resident set walked
    again for each set's candidate.  Kept as the oracle of victim order."""

    def _pick_victim(self):
        pol = self.cfg.policy
        evictable = [p for p in self.resident if p not in self._pinned]
        if not evictable:
            raise RuntimeError("every resident page is pinned")
        if pol == "lru":
            return evictable[0]
        if pol == "mru":
            return evictable[-1]
        if pol == "lfu":
            return min(evictable, key=lambda p: (self.meta[p].freq,
                                                 self.meta[p].last_tick))
        by_set = defaultdict(set)
        for p in evictable:
            by_set[self.meta[p].locality_set].add(p)
        best, best_cost = None, None
        for ls, pages in by_set.items():
            ordered = [p for p in self.resident if p in pages]
            cand = ordered[-1] if pol.endswith("mru") else ordered[0]
            if pol.startswith("optimized"):
                pr = self._p_reuse_eq2(cand)
            else:
                pr = self._p_reuse_set(ls)
            cost = self._cost(pr)
            if best_cost is None or cost < best_cost:
                best, best_cost = cand, cost
        return best


class _Backing:
    """A backing tier that logs evictions and fails a load or an
    eviction once when told to."""

    def __init__(self):
        self.evicted = []
        self.fail_load = self.fail_evict = False

    def load(self, page):
        if self.fail_load:
            self.fail_load = False
            raise OSError("load failed")

    def load_group(self, pages):
        self.load(pages[0])

    def evict(self, page):
        self.evicted.append(page)
        if self.fail_evict:
            self.fail_evict = False
            raise OSError("evict failed")


_N_PAGES, _CAP = 30, 10
_ACTIVE = ("m0", "m1", "m2", "m3")       # "idle" never accesses: lambda 0


def _fleet_maps(rng):
    models = _ACTIVE + ("idle",)
    sharers = {p: [models[i] for i in
                   rng.choice(5, size=int(rng.integers(1, 4)), replace=False)]
               for p in range(_N_PAGES)}
    for p in range(0, _N_PAGES, 7):
        sharers[p] = ["idle"]                # Eq. 2 cost c_w: ties
    # about one page in six has no set of its own (it is its own set)
    locality = {p: f"L{int(rng.integers(4))}" for p in range(_N_PAGES)
                if rng.random() > 0.15}
    return sharers, locality


def _ops(rng, steps=300):
    ops = []
    for _ in range(steps):
        r = rng.random()
        m = _ACTIVE[int(rng.integers(len(_ACTIVE)))]
        pages = [int(x) for x in rng.integers(_N_PAGES,
                                              size=int(rng.integers(1, 8)))]
        if r < 0.40:
            op = ("access", m, pages[0])
        elif r < 0.60:
            k = int(rng.integers(1, _CAP + 3))   # sometimes over capacity
            op = ("group", m, [int(x) for x in rng.integers(_N_PAGES,
                                                            size=k)])
        elif r < 0.75:
            op = ("prefetch", m if rng.random() < 0.7 else "idle", pages[0])
        elif r < 0.82:
            op = ("deferred", m, pages)
        elif r < 0.85:
            op = ("repack", _fleet_maps(rng)[1])
        else:
            inner = ("group", m, pages) if rng.random() < 0.5 \
                else ("access", m, pages[0])
            op = ("fail_evict" if rng.random() < 0.3 else "fail_load",
                  inner)
        ops.append(op)
    return ops


def _apply(pool, backing, op):
    kind = op[0]
    if kind in ("fail_load", "fail_evict"):
        setattr(backing, kind, True)
        try:
            return _apply(pool, backing, op[1])
        finally:
            backing.fail_load = backing.fail_evict = False
    try:
        if kind == "access":
            pool.access(op[1], op[2])
        elif kind == "group":
            pool.access_group(op[1], op[2])
        elif kind == "prefetch":
            pool.prefetch(op[1], op[2])
        elif kind == "deferred":
            with pool.deferred_loads():
                for p in op[2]:
                    pool.access(op[1], p)
        else:                                # a repack, as _sync_store does
            pool.invalidate_resident()
            pool.page_locality = dict(op[1])
            pool.meta.clear()
    except (ValueError, OSError) as e:
        return type(e).__name__
    return None


def _state(pool, backing, exc):
    return (exc, list(pool.resident), pool.hits, pool.misses,
            pool.evictions, pool.prefetches, pool.prefetch_declined,
            list(backing.evicted))


def _check_index(pool):
    """The index, where built, is what a fresh build from ``resident``
    would give."""
    if pool._sets is None:
        return
    want = defaultdict(list)
    for p in pool.resident:
        if p not in pool._pinned:
            want[pool.meta[p].locality_set].append(p)
    assert {ls: list(ps) for ls, ps in pool._sets.items()} == dict(want)
    pos = [pool._pos[p] for p in pool.resident]
    assert pos == sorted(pos) and len(pool._pos) == len(pos)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("policy", POLICIES)
def test_victim_order_matches_scan_oracle(policy, seed):
    """Seeded random traces: pins, prefetch at either end, repacks that
    reassign the locality sets, failed loads and evictions, and cost ties
    (lambda-0 sharers; seed 1 saturates Eq. 2, seed 2 sets c_r = 0).
    The indexed pool evicts the same pages, in the same order, as the
    scan, and keeps the same ``resident`` order, step by step."""
    kw = [{}, {"horizon_t": 1e4}, {"c_r": 0.0}][seed]
    for grouped in (True, False):
        rng = np.random.default_rng([seed, grouped])
        sharers, locality = _fleet_maps(rng)
        pools = []
        for cls in (BufferPool, _ScanPool):
            b = _Backing()
            pools.append((cls(PoolConfig(_CAP, policy, **kw),
                              page_sharers=sharers, page_locality=locality,
                              on_load=b.load, on_evict=b.evict,
                              on_load_group=b.load_group if grouped
                              else None), b))
        (pool, b), (oracle, ob) = pools
        built = False
        for step, op in enumerate(_ops(rng)):
            got = _state(pool, b, _apply(pool, b, op))
            want = _state(oracle, ob, _apply(oracle, ob, op))
            assert got == want, f"step {step}: {op}"
            _check_index(pool)
            built |= pool._sets is not None
        assert oracle._sets is None
        assert built == policy.startswith(("locality", "optimized"))


def _ds7b_pool(capacity):
    """The paging cell's pool: 2 families of 864 shared pages and 160
    per fine-tune, locality sets by equivalence class, optimized_mru."""
    sharers, locality, model_pages = {}, {}, {}
    pid = 0
    for fam in "ab":
        shared = list(range(pid, pid + 864))
        pid += 864
        for p in shared:
            sharers[p] = {f"{fam}-v0", f"{fam}-v1"}
            locality[p] = (fam, "shared")
        for v in ("v0", "v1"):
            own = list(range(pid, pid + 160))
            pid += 160
            for p in own:
                sharers[p] = {f"{fam}-{v}"}
                locality[p] = (fam, v)
            model_pages[f"{fam}-{v}"] = shared + own
    pool = BufferPool(PoolConfig(capacity, "optimized_mru"),
                      page_sharers=sharers, page_locality=locality)
    return pool, model_pages


def test_victim_scan_bound_and_lazy_index():
    order = ["a-v0", "a-v1", "b-v0", "b-v1"]
    pool, model_pages = _ds7b_pool(1480)
    for i in range(12):
        pool.access_group(order[i % 4], model_pages[order[i % 4]])
    # from the second base switch on: the paging cell's steady state
    assert pool.evictions > 3000
    assert pool.victim_scanned / pool.evictions <= 6     # 4306.2 by scans
    pool.reset_stats()
    index = pool._sets
    for i in range(8):
        pool.access_group(order[i % 4], model_pages[order[i % 4]])
        assert pool._sets is index           # maintained, never rebuilt
    assert (pool.hits, pool.misses) == (5280, 2912)      # as by scans
    assert round(100 * pool.misses / (pool.hits + pool.misses), 1) == 35.5
    for i in range(4):                       # pins released in any order
        pool.access_group(order[i % 4], model_pages[order[i % 4]][::-1])
        assert pool._sets is index
    pool.invalidate_resident()
    assert pool._sets is None and not pool.resident
    # a pool that holds every page never evicts and builds no index
    whole, model_pages = _ds7b_pool(2 * 1184)
    for i in range(8):
        whole.access_group(order[i % 4], model_pages[order[i % 4]])
    assert whole.evictions == 0 and whole.victim_scanned == 0
    assert whole._sets is None
