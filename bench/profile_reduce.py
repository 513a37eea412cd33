"""Reduce a ``jax.profiler`` trace of a slice of the window to numbers.

The traced slice is bracketed by two host annotations,
``bench_slice_begin`` and ``bench_slice_end``. Inside it, for every device
plane of the chip (``chips.json``: plane prefix and op lines):

* busy time is the union of the intervals in which an operation ran;
* the idle gaps are what that union leaves of the slice, cut where host
  spans open or close and each piece named by the innermost host span
  open in it (``plan``, ``trace_stage``, ``fetch``, ... or ``none``);
* the operations that took most time are summed by their HLO names, each
  by its self time (less the ops nested in it).

Busy time and operation times are averaged over the devices. The host
spans come in on the host clock; ``offset_ns`` maps them onto the trace's
clock (trace time = host perf_counter ns + offset).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Sequence, Tuple

BEGIN, END = "bench_slice_begin", "bench_slice_end"


def _host_events(pd, name: str):
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name:
                    yield ev


def slice_bounds(pd) -> Tuple[float, float]:
    """(start, end) of the slice on the trace's clock, in ns."""
    begin = next(_host_events(pd, BEGIN), None)
    end = next(_host_events(pd, END), None)
    if begin is None or end is None:
        raise ValueError("trace lacks the slice markers")
    return float(begin.start_ns), float(end.end_ns)


def op_name(text: str) -> str:
    """The HLO instruction name of an op event (``%fusion.12 = ...`` ->
    ``fusion.12``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def device_ops(pd, plane_prefix: str, op_lines: Sequence[str]
               ) -> Dict[str, List[Tuple[float, float, str]]]:
    """{device plane: [(start_ns, end_ns, op name)]} of every op event.
    Ops nest (a ``while`` holds its body's ops)."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        evs = []
        for line in plane.lines:
            if line.name not in op_lines:
                continue
            for ev in line.events:
                s = float(ev.start_ns)
                evs.append((s, s + float(ev.duration_ns),
                            op_name(ev.name)))
        if evs:
            out[plane.name] = evs
    return out


def self_times(evs: Iterable[Tuple[float, float, str]], lo: float,
               hi: float) -> Dict[str, float]:
    """Per op name, the time inside [lo, hi] that no nested op covers."""
    out: Dict[str, float] = {}
    stack: List[List] = []          # [end, name, self time so far]

    def close(item):
        out[item[1]] = out.get(item[1], 0.0) + item[2]

    for s, e, name in sorted(((max(s, lo), min(e, hi), n)
                              for s, e, n in evs), key=lambda x: (x[0], -x[1])):
        if e <= s:
            continue
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    for item in stack:
        close(item)
    return out


def union(intervals: Iterable[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    merged: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps_of(busy: Sequence[Tuple[float, float]], lo: float, hi: float
            ) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(mid: float, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The innermost (shortest) host span open at ``mid``, or ``none``."""
    best, width = "none", float("inf")
    for name, s, e in spans:
        if s <= mid <= e and e - s < width:
            best, width = name, e - s
    return best


def split(gap: Tuple[float, float], spans: Sequence[Tuple[str, float, float]]
          ) -> List[Tuple[str, float]]:
    """A gap cut at the host spans' edges: (innermost open span, length)
    for each piece, neighbouring pieces of one name joined."""
    s, e = gap
    cuts = sorted({s, e} | {t for _, a, b in spans for t in (a, b)
                            if s < t < e})
    pieces: List[List] = []
    for a, b in zip(cuts, cuts[1:]):
        name = label((a + b) / 2, spans)
        if pieces and pieces[-1][0] == name:
            pieces[-1][1] += b - a
        else:
            pieces.append([name, b - a])
    return [(n, d) for n, d in pieces]


def reduce(pd, spans: Sequence[Tuple[str, float, float]], offset_ns: float,
           plane_prefix: str, op_lines: Sequence[str], top: int = 10
           ) -> dict:
    """Busy and idle time of the slice, its longest idle gaps and the ops
    that took most time. ``spans``: (name, start_s, end_s) on the host's
    perf_counter clock."""
    lo, hi = slice_bounds(pd)
    ops = device_ops(pd, plane_prefix, op_lines)
    if not ops:
        raise ValueError(f"no device op events under {plane_prefix!r} "
                         f"lines {list(op_lines)}")
    host = [(n, s * 1e9 + offset_ns, e * 1e9 + offset_ns)
            for n, s, e in spans]
    busy_total, per_op = 0.0, {}
    gaps = []
    for i, (plane, evs) in enumerate(sorted(ops.items())):
        busy = union(((s, e) for s, e, _ in evs), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for name, d in self_times(evs, lo, hi).items():
            per_op[name] = per_op.get(name, 0.0) + d
        if i == 0:
            gaps = [piece for g in gaps_of(busy, lo, hi)
                    for piece in split(g, host)]
    n = len(ops)
    window = hi - lo
    busy_ns = busy_total / n
    return {
        "window_s": window / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": 100.0 * (1.0 - busy_ns / window),
        "devices": n,
        "device_ops": [[k, v / n / 1e9] for k, v in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / 1e9] for k, v in sorted(
            gaps, key=lambda kv: -kv[1])[:top]],
        "idle_by_span": _by_span(gaps),
    }


def _by_span(gaps) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for k, v in gaps:
        out[k] = out.get(k, 0.0) + v / 1e9
    return out
