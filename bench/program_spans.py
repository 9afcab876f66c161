"""The program's own host spans in the profiler trace of a traced run.

While a ``jax.profiler`` trace records, ``repro.obs`` times the
program's spans on the host clock with no set-up from the benchmark:
each span the program opens is also a ``jax.profiler.TraceAnnotation``
named ``repro.<span>``, so it lands in the same ``.xplane.pb`` as the
device's operations and the benchmark's ``bench.*`` wrappers.  This
module reads the ``repro.*`` events back from the trace ``run.py``
leaves under ``.scratch/bench/trace/<cell>`` (:func:`load`), with the
pool's counters that each ``repro.evict`` carries (:func:`stamps`), and
reduces them with ``reduce.py``'s interval arithmetic.  A program
without these spans traces nothing here, and every reader returns
``None``.

    python3 bench/program_spans.py .scratch/bench/trace/ds7b-embed.hot \\
        --requests 3800

prints, as one JSON object, the device's idle seconds by the innermost
``repro.*`` span open, and each ``bench.*`` wrapper's milliseconds per
request beside those of the program span at the same boundary.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

from reduce import Interval, find_xplane, self_seconds

PREFIX = "repro."
#: where ``run.py`` leaves a traced run's profile, one directory a cell
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".scratch", "bench", "trace")
#: the spans whose arguments are kept: the counters they carry
STAMPED = ("repro.evict",)

#: each ``bench.*`` wrapper (``spans.py``) and the program span opened at
#: the same boundary
AGREEMENT = (
    ("bench.submit", "repro.submit"),
    ("bench.engine.run", "repro.run"),
    ("bench.fetch", "repro.fault_group"),
    ("bench.storage_fetch", "repro.get_pages"),
    ("bench.stage", "repro.stage"),
    ("bench.transfer_commit", "repro.load_group"),
    ("bench.page_load", "repro.page_load"),
    ("bench.prestage", "repro.prestage"),
    ("bench.gather", "repro.gather"),
    ("bench.head", "repro.head"),
)


def _name(event_name: str) -> str:
    """A span's name without annotation arguments (``name#k=v#``)."""
    return event_name.split("#", 1)[0]


Stamp = Tuple[float, Dict[str, float]]
_CACHE: Dict[str, Tuple[List[Interval], Dict[str, List[Stamp]]]] = {}


def _read(log_dir: str):
    path = find_xplane(log_dir)
    if path not in _CACHE:
        from jax.profiler import ProfileData
        spans: List[Interval] = []
        stamped: Dict[str, List[Stamp]] = {n: [] for n in STAMPED}
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for ln in plane.lines:
                for e in ln.events:
                    name = _name(e.name)
                    if name.startswith(PREFIX):
                        spans.append((e.start_ns * 1e-9, e.end_ns * 1e-9,
                                      name))
                        if name in stamped:
                            stamped[name].append((e.end_ns * 1e-9,
                                                  dict(e.stats)))
        spans.sort()
        for marks in stamped.values():
            marks.sort(key=lambda m: m[0])
        _CACHE[path] = spans, stamped
    return _CACHE[path]


def load(log_dir: str) -> List[Interval]:
    """``(start_s, end_s, name)`` of every ``repro.*`` host event in the
    newest trace under ``log_dir``, sorted."""
    return _read(log_dir)[0]


def trace_dir(run) -> Optional[str]:
    """The profile of the traced run ``run`` (a reader's argument), or
    ``None`` where it left none."""
    path = os.path.join(TRACE_ROOT, run.cell["name"])
    return path if os.path.isdir(path) else None


def spans_of(run) -> List[Interval]:
    d = trace_dir(run)
    try:
        return load(d) if d else []
    except FileNotFoundError:
        return []


def stamps(run, name: str) -> List[Stamp]:
    """``(end_s, args)`` of each span ``name`` (one of :data:`STAMPED`)
    that closed inside the traced window, in order."""
    d = trace_dir(run)
    try:
        marks = _read(d)[1].get(name, []) if d else []
    except FileNotFoundError:
        return []
    return [m for m in marks if run.lo <= m[0] <= run.hi]


def ms_per_req(run, name: str) -> Optional[float]:
    """Milliseconds per request answered inside the spans ``name`` of a
    traced run (nested ones counted once); ``None`` where no such span
    was recorded."""
    spans = spans_of(run)
    if not run.requests or not any(n == name for _, _, n in spans):
        return None
    return self_seconds(spans, name, (), run.lo, run.hi) * 1e3 \
        / run.requests


def agreement(bench_spans, spans, requests: int, lo: float, hi: float
              ) -> Dict[str, List[float]]:
    """``{wrapper: [bench ms/req, program ms/req]}`` for every pair of
    :data:`AGREEMENT` of which either side was recorded."""
    out = {}
    for b, p in AGREEMENT:
        bm = self_seconds(bench_spans, b, (), lo, hi) * 1e3 / requests
        pm = self_seconds(spans, p, (), lo, hi) * 1e3 / requests
        if bm or pm:
            out[b] = [bm, pm]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--requests", type=int, required=True,
                    help="requests answered in the window")
    args = ap.parse_args(argv)
    import reduce
    tr = reduce.load(args.trace_dir)
    lo, hi = reduce.window_of(tr.spans)
    spans = load(args.trace_dir)
    idle = reduce.gaps_by_span(tr.device, spans, lo, hi)
    print(json.dumps({
        "window_s": hi - lo,
        "busy_s": reduce.busy_seconds(tr.device, lo, hi),
        "idle_by_program_span": reduce.top(idle, len(idle)),
        "agreement_ms_per_req": agreement(tr.spans, spans, args.requests,
                                          lo, hi),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
