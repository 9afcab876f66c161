"""store_mb_per_variant: bytes of the deduplicated page set the store
committed to SQLite (summed by the benchmark from the file), in MB
(10^6 bytes), over the number of variants it holds."""


def read(run):
    return run.store_bytes / 1e6 / run.variants
