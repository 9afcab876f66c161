"""pages_per_req: pages the engine touches per request (its page set
after packing; the program's ``BufferPool`` hit and miss counters) in
the traced window."""


def read(run):
    if not run.requests:
        return None
    return (run.counters["hits"] + run.counters["misses"]) / run.requests
