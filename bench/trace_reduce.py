"""Reduce a profiler trace of one measured window to numbers.

A trace is read into a plain structure first (:func:`load_xplane`), so
the reduction can be checked on a small recorded trace without the
profiler:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]},
                           ...]},
                ...]}

The window is the host event ``bench.window``; the other ``bench.*``
host events are the benchmark's spans around its calls into each layer.
Device time comes from the ``XLA Ops`` line of each device plane (busy
time is the union of its intervals; an op's own time leaves out the ops
nested in it) and per-program time from its ``XLA Modules`` line.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: label of an idle gap during which no benchmark span was open
UNATTRIBUTED = "loop"

Interval = Tuple[float, float]


def load_xplane(log_dir: str) -> Dict:
    """The newest ``*.xplane.pb`` under ``log_dir`` as a plain structure."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {log_dir!r}")
    data = ProfileData.from_file(paths[-1])
    return {"planes": [
        {"name": plane.name,
         "lines": [{"name": line.name,
                    "events": [[e.name, float(e.start_ns),
                                float(e.duration_ns)]
                               for e in line.events]}
                   for line in plane.lines]}
        for plane in data.planes]}


def _is_device(plane: Dict) -> bool:
    return plane["name"].startswith("/device:") and any(
        line["name"] == OPS_LINE for line in plane["lines"])


def _line(plane: Dict, name: str) -> List:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def op_name(event_name: str) -> str:
    """A device op event's HLO instruction name and opcode
    (``%fusion.59 = bf16[..] fusion(..)`` -> ``%fusion.59 fusion``)."""
    lhs, sep, rhs = event_name.partition(" = ")
    if not sep:
        return event_name
    m = re.search(r"\s([a-z][\w-]*)\(", rhs)
    return f"{lhs} {m.group(1)}" if m else lhs


def _self_times(events) -> Dict[str, float]:
    """Time of each op not covered by ops nested inside it (a while
    loop's body ops run inside the loop's own event)."""
    out: Dict[str, float] = collections.Counter()
    stack: List[List] = []                    # [end, name, child time]
    for start, neg_dur, name in sorted((s, -d, n) for n, s, d in events):
        end = start - neg_dur
        while stack and stack[-1][0] <= start:
            e, n, child = stack.pop()
            out[n] -= child
        if stack:
            stack[-1][2] += min(end, stack[-1][0]) - start
        out[name] += end - start
        stack.append([end, name, 0.0])
    for e, n, child in stack:
        out[n] -= child
    return out


def host_spans(trace: Dict) -> List[Tuple[str, float, float]]:
    """Every ``bench.*`` host event as (name, start_ns, end_ns)."""
    spans = []
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    spans.append((name, start, start + dur))
    return spans


def _labels(spans, times: List[float]) -> List[str]:
    """The innermost benchmark span open at each of the sorted ``times``."""
    spans = sorted((s, e, name) for name, s, e in spans if name != WINDOW)
    out, active, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][0] <= t:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] > t]
        if active:
            name = min(active, key=lambda sp: sp[1] - sp[0])[2]
            out.append(name[len(SPAN_PREFIX):])
        else:
            out.append(UNATTRIBUTED)
    return out


def reduce(trace: Dict) -> Dict:
    """Window length, device busy time (mean over devices), time per
    device program and per op, and idle time by the host span open
    during it; all times in seconds."""
    spans = host_spans(trace)
    windows = [(s, e) for name, s, e in spans if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} host event, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    devices = [p for p in trace["planes"] if _is_device(p)]
    if not devices:
        raise ValueError("the trace holds no device plane with an "
                         f"{OPS_LINE!r} line")
    busy = 0.0
    modules: Dict[str, float] = collections.Counter()
    ops: Dict[str, float] = collections.Counter()
    idle: Dict[str, float] = collections.Counter()
    for plane in devices:
        events = [(op_name(n), max(s, lo), min(s + d, hi) - max(s, lo))
                  for n, s, d in _line(plane, OPS_LINE)
                  if s + d > lo and s < hi]
        ops.update(_self_times(events))
        intervals = [(s, s + d) for _, s, d in events]
        covered = _union(intervals)
        busy += sum(e - s for s, e in covered)
        edges = [lo] + [x for iv in covered for x in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        for (s, e), label in zip(gaps, _labels(
                spans, [(s + e) / 2 for s, e in gaps])):
            idle[label] += e - s
        for name, start, dur in _line(plane, MODULES_LINE):
            if start + dur > lo and start < hi:
                modules[name] += dur
    n = len(devices)
    scale = 1e-9 / n
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * scale,
        "devices": n,
        "modules": {k: v * scale for k, v in modules.items()},
        "ops": {k: v * scale for k, v in ops.items()},
        "idle": {k: v * scale for k, v in idle.items()},
    }


def top(table: Dict[str, float], n: int = 10) -> List[List]:
    """The ``n`` largest entries as [[name, seconds], ...]."""
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]
