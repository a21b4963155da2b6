"""Reduce a JAX profiler trace to device busy time, idle gaps by host span,
and the device operations that took most time.

The trace is read with ``jax.profiler.ProfileData`` into plain event dicts
(:func:`load_events`), and everything after that works on those dicts, so a
small recorded trace can stand in for a real one in the tests.

* Device events are those on planes named ``/device:TPU:<n>``, on the line
  ``XLA Ops`` (the operations the device ran).  Busy time is the union of
  their intervals inside the window, averaged over the devices that ran
  something.
* The window is the host span named :data:`WINDOW` that the benchmark
  opens around the traced steps.
* Idle time is the window minus the busy union; each idle gap is credited
  to the benchmark's host spans it overlaps (``loss_and_grads``,
  ``optim_update``), and what no span covers to
  ``outside_any_span``.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OUTSIDE = "outside_any_span"
_SUFFIX = re.compile(r"[.\-_]?\d+$")
_SHAPE = re.compile(r"=\s*\(?([a-z]+\d*)\[([\d,]*)\]")
_INSTR = re.compile(r"^%([\w.\-]+)\s*=")


@dataclasses.dataclass
class TraceSummary:
    busy_s: float
    window_s: float
    devices: int
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def load_events(trace_dir: str, host_spans: Iterable[str]) -> List[dict]:
    """The device ops and the named host spans of the newest trace under
    ``trace_dir``, as dicts with plane, line, name, start/duration (ns) and
    the op's ``long_name`` where the trace has one."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    wanted = set(host_spans) | {WINDOW}
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        if not on_device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if on_device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if not on_device and ev.name not in wanted:
                    continue
                rec = {"plane": plane.name, "line": line.name,
                       "name": ev.name, "start_ns": float(ev.start_ns),
                       "dur_ns": float(ev.duration_ns)}
                if on_device:
                    stats = dict(ev.stats)
                    if "long_name" in stats:
                        rec["long_name"] = str(stats["long_name"])
                out.append(rec)
    return out


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(intervals, lo: float, hi: float):
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


def op_key(ev: dict) -> str:
    """The name an op is grouped under: its HLO name without the trailing
    instance number, with the dtype and shape of its (first) result when
    the trace gives them (``fusion_f32_1024_512_4_4``).  On the TPU the
    event's name is the whole HLO instruction (``%fusion.3 = f32[...]...``);
    elsewhere the instruction is in the ``long_name`` stat."""
    text = ev["name"]
    m = _INSTR.match(text)
    if m:
        base = m.group(1)
    else:
        base, text = text, ev.get("long_name", "")
    base = _SUFFIX.sub("", base) or base
    m = _SHAPE.search(text)
    if m:
        dims = "_".join(d for d in m.group(2).split(",") if d)
        return f"{base}_{m.group(1)}_{dims}" if dims else f"{base}_{m.group(1)}"
    return base


def reduce_events(events: Sequence[dict], top: int = 10
                  ) -> Optional[TraceSummary]:
    """The summary of one traced window, or None when the trace holds no
    window or no device operation in it."""
    windows = [e for e in events if e["name"] == WINDOW
               and not DEVICE_PLANE.match(e["plane"])]
    if not windows:
        return None
    w = max(windows, key=lambda e: e["dur_ns"])
    w0, w1 = w["start_ns"], w["start_ns"] + w["dur_ns"]
    per_device: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    op_time: Dict[str, float] = defaultdict(float)
    spans: List[Tuple[float, float, str]] = []
    for e in events:
        a, b = e["start_ns"], e["start_ns"] + e["dur_ns"]
        if DEVICE_PLANE.match(e["plane"]):
            per_device[e["plane"]].append((a, b))
            for ca, cb in _clip([(a, b)], w0, w1):
                op_time[op_key(e)] += cb - ca
        elif e["name"] != WINDOW:
            spans.append((a, b, e["name"]))
    busy_by_device = {}
    for dev, iv in per_device.items():
        merged = _union(_clip(iv, w0, w1))
        busy = sum(b - a for a, b in merged)
        if busy > 0:
            busy_by_device[dev] = merged
    if not busy_by_device:
        return None
    n_dev = len(busy_by_device)
    busy_ns = sum(sum(b - a for a, b in m)
                  for m in busy_by_device.values()) / n_dev

    gap_time: Dict[str, float] = defaultdict(float)
    for merged in busy_by_device.values():
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for ga, gb in zip(edges[::2], edges[1::2]):
            if gb <= ga:
                continue
            covered = 0.0
            for sa, sb, name in spans:
                ov = min(gb, sb) - max(ga, sa)
                if ov > 0:
                    gap_time[name] += ov / n_dev
                    covered += ov
            rest = (gb - ga) - covered
            if rest > 0:
                gap_time[OUTSIDE] += rest / n_dev

    def ranked(d):
        items = sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return [(k, v / 1e9) for k, v in items]

    return TraceSummary(
        busy_s=busy_ns / 1e9, window_s=(w1 - w0) / 1e9, devices=n_dev,
        device_ops=[(k, v / n_dev) for k, v in ranked(op_time)],
        idle_gaps=ranked(gap_time))
