"""The program's own spans in a profiler trace: self time, coverage, and
the device's idle time put down to the innermost span open during it.

Works on the event dicts of :func:`chipbench.devtrace.load_events`, called
with the span names the program opens (``repro.runtime.trace.SPANS``)
beside the benchmark's own.  Spans nest on their host thread (one
``plane``/``line`` pair): a span's self time is its duration less the part
its child spans cover, and an instant of idle device time belongs to the
span that opened last among those still open on its thread.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from chipbench.devtrace import DEVICE_PLANE, OUTSIDE, WINDOW, _clip, _union


def _window(events: Sequence[dict]) -> Optional[Tuple[float, float]]:
    ws = [e for e in events if e["name"] == WINDOW
          and not DEVICE_PLANE.match(e["plane"])]
    if not ws:
        return None
    w = max(ws, key=lambda e: e["dur_ns"])
    return w["start_ns"], w["start_ns"] + w["dur_ns"]


def _threads(events: Sequence[dict], names: Iterable[str]
             ) -> Dict[Tuple[str, str], List[Tuple[float, float, str]]]:
    """Per host thread, the spans named in ``names`` as ``(start, end,
    name)``, parents before their children."""
    wanted = set(names)
    out: Dict[Tuple[str, str], List[Tuple[float, float, str]]] = \
        defaultdict(list)
    for e in events:
        if e["name"] in wanted and not DEVICE_PLANE.match(e["plane"]):
            a = e["start_ns"]
            out[(e["plane"], e["line"])].append((a, a + e["dur_ns"],
                                                 e["name"]))
    for spans in out.values():
        spans.sort(key=lambda s: (s[0], -s[1]))
    return out


def self_times(events: Sequence[dict], names: Iterable[str]
               ) -> Dict[str, Dict[str, float]]:
    """Per span name, ``count``, ``total_s`` and ``self_s`` (the duration
    less what its direct children cover) of the spans that start inside
    the window, or anywhere when the trace holds no window."""
    win = _window(events)
    out: Dict[str, Dict[str, float]] = {}
    for spans in _threads(events, names).values():
        stack: List[List] = []          # [start, end, name, child ns]

        def close(entry) -> None:
            a, b, name, child = entry
            if win is not None and not win[0] <= a < win[1]:
                return
            d = out.setdefault(name, {"count": 0, "total_s": 0.0,
                                      "self_s": 0.0})
            d["count"] += 1
            d["total_s"] += (b - a) / 1e9
            d["self_s"] += max(0.0, (b - a) - child) / 1e9

        for a, b, name in spans:
            while stack and stack[-1][1] <= a:
                close(stack.pop())
            if stack:
                parent = stack[-1]
                parent[3] += min(b, parent[1]) - a
            stack.append([a, b, name, 0.0])
        while stack:
            close(stack.pop())
    return out


def coverage(events: Sequence[dict], outer: str,
             names: Iterable[str]) -> Optional[float]:
    """The share of the time of the spans named ``outer`` (inside the
    window) that the spans named in ``names`` cover; None without an
    ``outer`` span."""
    win = _window(events)
    lo, hi = win if win is not None else (float("-inf"), float("inf"))
    threads = _threads(events, set(names) | {outer})
    total = covered = 0.0
    for spans in threads.values():
        inner = _union((a, b) for a, b, n in spans if n != outer)
        for a, b, n in spans:
            if n != outer:
                continue
            for oa, ob in _clip([(a, b)], lo, hi):
                total += ob - oa
                covered += sum(cb - ca for ca, cb in _clip(inner, oa, ob))
    return covered / total if total > 0 else None


def idle_by_innermost(events: Sequence[dict], names: Iterable[str]
                      ) -> List[Tuple[str, float]]:
    """Idle device seconds inside the window, each instant credited to the
    innermost of the spans named in ``names`` open at it (the one opened
    last, over every host thread), else to ``outside_any_span``; averaged
    over the devices that ran something, largest first."""
    win = _window(events)
    if win is None:
        return []
    w0, w1 = win
    per_device: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for e in events:
        if DEVICE_PLANE.match(e["plane"]):
            per_device[e["plane"]].append(
                (e["start_ns"], e["start_ns"] + e["dur_ns"]))
    busy = {d: _union(_clip(iv, w0, w1)) for d, iv in per_device.items()}
    busy = {d: m for d, m in busy.items() if m}
    if not busy:
        return []
    # the host timeline cut into pieces, each owned by its innermost span
    marks = []
    for spans in _threads(events, names).values():
        for a, b, name in spans:
            marks.append((a, 1, b, name))
            marks.append((b, 0, a, name))
    marks.sort(key=lambda m: (m[0], m[1]))
    pieces: List[Tuple[float, float, str]] = []
    open_spans: Dict[Tuple[float, float, str], int] = {}
    t_prev = w0
    for t, is_start, other, name in marks:
        if t > t_prev and open_spans:
            owner = max(open_spans, key=lambda s: s[0])[2]
            pieces.append((t_prev, t, owner))
        t_prev = max(t_prev, t)
        key = (t, other, name) if is_start else (other, t, name)
        if is_start:
            open_spans[key] = open_spans.get(key, 0) + 1
        elif open_spans.get(key, 0) > 1:
            open_spans[key] -= 1
        else:
            open_spans.pop(key, None)
    n_dev = len(busy)
    credit: Dict[str, float] = defaultdict(float)
    for merged in busy.values():
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps = [(ga, gb) for ga, gb in zip(edges[::2], edges[1::2])
                if gb > ga]
        for ga, gb in gaps:
            covered = 0.0
            for pa, pb, owner in _clip_pieces(pieces, ga, gb):
                credit[owner] += (pb - pa) / n_dev
                covered += pb - pa
            if gb - ga - covered > 0:
                credit[OUTSIDE] += (gb - ga - covered) / n_dev
    return sorted(((k, v / 1e9) for k, v in credit.items()),
                  key=lambda kv: -kv[1])


def _clip_pieces(pieces, lo: float, hi: float):
    """The pieces (sorted, disjoint) that overlap ``[lo, hi)``, clipped."""
    i = max(0, bisect.bisect_right(pieces, (lo,)) - 1)
    while i < len(pieces) and pieces[i][0] < hi:
        a, b, owner = pieces[i]
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b, owner
        i += 1
