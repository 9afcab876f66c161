"""serve_mfu: the whole serving step's share of the chip's peak FLOP/s.

A document costs ``doc_len * width`` adds to pool its tokens and
``2 * width * classes`` for the head; times documents answered per
second in the traced window, over the peak (``peaks.py``: the bf16 peak,
197 TFLOP/s on a v5e)."""


def flops_per_doc(run) -> int:
    width = run.width
    return run.mix["doc_len"] * width + 2 * width * run.cfg["classes"]


def read(run):
    if not run.requests or run.hi <= run.lo:
        return None
    rate = run.docs / (run.hi - run.lo)
    return 100.0 * flops_per_doc(run) * rate / run.peaks["peak_flops"]
