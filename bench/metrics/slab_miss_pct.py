"""slab_miss_pct: buffer-pool misses over pages touched in the traced
window, in percent (the program's ``BufferPool`` counters)."""


def read(run):
    n = run.counters["hits"] + run.counters["misses"]
    if not n:
        return None
    return 100.0 * run.counters["misses"] / n
