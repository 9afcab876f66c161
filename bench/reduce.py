"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A :class:`Trace` holds two lists of ``(start_s, end_s, name)`` intervals
on the profiler's clock:

  * ``device``: every operation that ran on the first TPU chip, named
    ``<module>:<op>`` (``jit_dedup_embedding:dedup_embedding``), from the
    device plane's ``XLA Ops`` line;
  * ``spans``: the host spans the benchmark opened (``bench.*``), from
    the host plane.

The functions below are plain interval arithmetic over those lists:
the union of device intervals (busy time), its complement (idle gaps),
the innermost host span open at each moment (what the host was doing in
a gap), a span's self time, and device seconds per op or per module.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Sequence, Tuple

Interval = Tuple[float, float, str]
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


class Trace(NamedTuple):
    device: List[Interval]
    spans: List[Interval]

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls([tuple(e) for e in d["device"]],
                   [tuple(e) for e in d["spans"]])


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def op_name(hlo: str) -> str:
    """An op's short name from the trace's HLO text: ``%copy.9 = f32[..]
    copy(..)`` -> ``copy`` (the instruction's name, without its number)."""
    name = hlo.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(log_dir: str, device_index: int = 0) -> Trace:
    """Read the newest trace under ``log_dir`` (``jax.profiler``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(log_dir))
    device: List[Interval] = []
    spans: List[Interval] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            if int(m.group(1)) != device_index:
                continue
            lines = {ln.name: ln for ln in plane.lines}
            modules = []
            if MODULES_LINE in lines:
                modules = sorted((e.start_ns, e.end_ns,
                                  _module_name(e.name))
                                 for e in lines[MODULES_LINE].events)
            starts = [m_[0] for m_ in modules]
            ops = lines.get(OPS_LINE)
            if ops is None:
                continue
            for e in ops.events:
                # an op belongs to the program running when it started
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = modules[i][2] if i >= 0 else "?"
                device.append((e.start_ns * 1e-9, e.end_ns * 1e-9,
                               f"{mod}:{op_name(e.name)}"))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns * 1e-9, e.end_ns * 1e-9,
                                      e.name))
    device.sort()
    spans.sort()
    return Trace(device, spans)


# ------------------------------------------------------------ intervals --
def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi), n) for s, e, n in intervals
            if e > lo and s < hi]


def union(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged ``(start, end)`` cover of the intervals inside ``[lo, hi]``."""
    out: List[List[float]] = []
    for s, e, _ in sorted(clip(intervals, lo, hi)):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(device: Sequence[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(device, lo, hi))


def idle_gaps(device: Sequence[Interval], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The complement of the device's busy cover inside ``[lo, hi]``."""
    gaps, t = [], lo
    for s, e in union(device, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def innermost(spans: Sequence[Interval], lo: float, hi: float
              ) -> List[Interval]:
    """``(start, end, name)`` pieces covering ``[lo, hi]``, each labelled
    with the innermost host span open there (the open span opened last),
    or ``unannotated`` where none is.  One sweep over the boundaries."""
    bounds = []
    for i, (s, e, n) in enumerate(clip(spans, lo, hi)):
        bounds.append((s, 1, i, n))
        bounds.append((e, 0, i, n))
    bounds.sort()                        # at a tie, ends before starts
    open_: List[Tuple[int, str]] = []    # (index, name), opening order
    out: List[Interval] = []
    t = lo

    def emit(until: float) -> None:
        label = open_[-1][1] if open_ else "unannotated"
        if until <= t:
            return
        if out and out[-1][2] == label and out[-1][1] == t:
            out[-1] = (out[-1][0], until, label)
        else:
            out.append((t, until, label))

    for time_, is_start, i, n in bounds:
        emit(time_)
        t = max(t, time_)
        if is_start:
            open_.append((i, n))
        else:
            open_.remove((i, n))
    emit(hi)
    return out


def gaps_by_span(device: Sequence[Interval], spans: Sequence[Interval],
                 lo: float, hi: float) -> Dict[str, float]:
    """Idle device seconds in ``[lo, hi]``, by the innermost host span
    open at the time (span names without the ``bench.`` prefix)."""
    pieces = innermost(spans, lo, hi)
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for g0, g1 in idle_gaps(device, lo, hi):
        while j < len(pieces) and pieces[j][1] <= g0:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < g1:
            s, e, n = pieces[k]
            overlap = min(e, g1) - max(s, g0)
            if overlap > 0:
                out[n[len(SPAN_PREFIX):] if n.startswith(SPAN_PREFIX)
                    else n] += overlap
            k += 1
    return dict(out)


def span_seconds(spans: Sequence[Interval], name: str, lo: float,
                 hi: float) -> float:
    return sum(e - s for s, e, n in clip(spans, lo, hi) if n == name)


def span_count(spans: Sequence[Interval], name: str, lo: float,
               hi: float) -> int:
    return sum(1 for s, e, n in spans if n == name and s >= lo and e <= hi)


def self_seconds(spans: Sequence[Interval], names,
                 children: Sequence[str], lo: float, hi: float) -> float:
    """Time inside the spans named ``names`` (one name or several; where
    they nest, counted once) not covered by a ``children`` span."""
    names = (names,) if isinstance(names, str) else tuple(names)
    inside = clip(spans, lo, hi)
    outer = union([x for x in inside if x[2] in names], lo, hi)
    kids = union([x for x in inside if x[2] in children], lo, hi)
    total, j = 0.0, 0
    for s, e in outer:
        total += e - s
        while j < len(kids) and kids[j][1] <= s:
            j += 1
        k = j
        while k < len(kids) and kids[k][0] < e:
            total -= min(e, kids[k][1]) - max(s, kids[k][0])
            k += 1
    return total


def op_seconds(device: Sequence[Interval], lo: float, hi: float
               ) -> Dict[str, float]:
    out: Dict[str, float] = defaultdict(float)
    for s, e, n in clip(device, lo, hi):
        out[n] += e - s
    return dict(out)


def module_seconds(device: Sequence[Interval], module: str, lo: float,
                   hi: float) -> float:
    """Device seconds of every op of one jitted program (``module``)."""
    key = module + ":"
    return sum(e - s for s, e, n in clip(device, lo, hi)
               if n.startswith(key))


def top(d: Dict[str, float], k: int = 10) -> List[list]:
    return [[n, v] for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]]


def window_of(spans: Sequence[Interval]) -> Tuple[float, float]:
    """The ``bench.window`` span's bounds."""
    w = [x for x in spans if x[2] == SPAN_PREFIX + "window"]
    if len(w) != 1:
        raise ValueError(f"expected one bench.window span, found {len(w)}")
    return w[0][0], w[0][1]
