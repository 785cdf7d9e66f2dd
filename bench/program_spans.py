"""Split a traced window by the program's own profiler spans.

The program marks its layers with ``repro.*`` host spans
(``jax.profiler.TraceAnnotation``; DESIGN.md §Tracing): ``repro.event``
per popped event, ``repro.strategy``, ``repro.alive``, ``repro.round``
with ``.h2d``, ``.keys`` and ``.launch``, ``repro.materialize``,
``repro.eval`` with ``.wait``, and ``repro.on_eval``.  They sit in the
same trace as the device's ``XLA Ops``, on its clock.  Given the plain
structure of :func:`bench.trace_reduce.load_xplane`, :func:`reduce`
returns, inside ``bench.window``:

* ``self_s``: each span name's self time, its duration clipped to the
  window less the part that child ``repro.*`` spans on the same host
  line cover;
* ``idle_s``: device-idle time split by overlap over the innermost
  program span open during it (the shortest of those open); idle time
  that no span covers goes to ``loop``;
* ``event_cover``: the share of the window that ``repro.event`` spans
  cover, counted from the first one that starts inside it (the event
  open when the profiler starts is not recorded).

A trace without ``repro.*`` spans (a program that has none) gives empty
tables and an ``event_cover`` of None.  All times are in seconds; idle
time is the mean over the trace's devices.
"""
from __future__ import annotations

import collections
from typing import Dict, List, Optional, Tuple

from bench import trace_reduce as tr

PREFIX = "repro."
EVENT = "repro.event"
#: label of device-idle time during which no program span was open
UNATTRIBUTED = tr.UNATTRIBUTED
#: each host cost of a committed update, as the spans whose self times
#: it sums: the loop and the strategy (pop, sampling, reschedule, the
#: Eq. 3 weights), the O(N) ``alive()`` mask, the key split, the copy
#: of the streamed batch to the device, and the launch of the fused step
PER_UPDATE = {
    "strategy_ms_per_update": ("repro.event", "repro.strategy"),
    "alive_ms_per_update": ("repro.alive",),
    "round_keys_ms_per_update": ("repro.round.keys",),
    "round_h2d_ms_per_update": ("repro.round.h2d",),
    "round_launch_ms_per_update": ("repro.round.launch",),
}

Span = Tuple[str, float, float]              # name, start_ns, end_ns


def _window(trace: Dict) -> Tuple[float, float]:
    windows = [(s, e) for name, s, e in tr.host_spans(trace)
               if name == tr.WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {tr.WINDOW} host event, found "
                         f"{len(windows)}")
    return windows[0]


def program_lines(trace: Dict, lo: float, hi: float) -> List[List[Span]]:
    """The ``repro.*`` host events of each host line, clipped to
    [``lo``, ``hi``]; lines without any are left out."""
    lines = []
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            spans = [(n, max(s, lo), min(s + d, hi))
                     for n, s, d in line["events"]
                     if n.startswith(PREFIX) and s + d > lo and s < hi]
            if spans:
                lines.append(spans)
    return lines


def innermost(spans: List[Span]) -> List[Span]:
    """Disjoint, sorted segments in which some span is open, each named
    by the innermost span open there (the shortest)."""
    bounds = sorted({x for _, s, e in spans for x in (s, e)})
    by_start = sorted(spans, key=lambda sp: sp[1])
    out: List[Span] = []
    active: List[Span] = []
    i = 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(by_start) and by_start[i][1] <= a:
            active.append(by_start[i])
            i += 1
        active = [sp for sp in active if sp[2] > a]
        if not active:
            continue
        name = min(active, key=lambda sp: sp[2] - sp[1])[0]
        if out and out[-1][0] == name and out[-1][2] == a:
            out[-1] = (name, out[-1][1], b)
        else:
            out.append((name, a, b))
    return out


def split_idle(gaps: List[Tuple[float, float]],
               segments: List[Span]) -> Dict[str, float]:
    """Idle time of the sorted, disjoint ``gaps`` by overlap with the
    named ``segments`` (sorted, disjoint); the rest to ``loop``."""
    idle: Dict[str, float] = collections.Counter()
    j = 0
    for gs, ge in gaps:
        while j < len(segments) and segments[j][2] <= gs:
            j += 1
        covered = 0.0
        k = j
        while k < len(segments) and segments[k][1] < ge:
            name, s, e = segments[k]
            part = min(ge, e) - max(gs, s)
            if part > 0:
                idle[name] += part
                covered += part
            k += 1
        if ge - gs > covered:
            idle[UNATTRIBUTED] += ge - gs - covered
    return idle


def device_gaps(plane: Dict, lo: float, hi: float
                ) -> List[Tuple[float, float]]:
    """Intervals of [``lo``, ``hi``] in which no op of ``plane`` runs."""
    busy = tr._union([(max(s, lo), min(s + d, hi))
                      for _, s, d in tr._line(plane, tr.OPS_LINE)
                      if s + d > lo and s < hi])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def event_cover(spans: List[Span], lo: float, hi: float):
    """Share of [first ``repro.event`` start inside the window, ``hi``]
    covered by ``repro.event`` spans; None without such a start."""
    events = [(s, e) for n, s, e in spans if n == EVENT]
    starts = [s for s, _ in events if s > lo]
    if not starts:
        return None
    first = min(starts)
    if hi <= first:
        return None
    covered = sum(e - s for s, e in tr._union(
        [(max(s, first), e) for s, e in events if e > first]))
    return covered / (hi - first)


def reduce(trace: Dict) -> Dict:
    """``self_s``, ``idle_s`` and ``event_cover`` of the window."""
    lo, hi = _window(trace)
    lines = program_lines(trace, lo, hi)
    self_ns: Dict[str, float] = collections.Counter()
    for spans in lines:
        self_ns.update(tr._self_times([(n, s, e - s) for n, s, e in spans]))
    spans = [sp for line in lines for sp in line]
    idle: Dict[str, float] = collections.Counter()
    devices = [p for p in trace["planes"] if tr._is_device(p)]
    if spans:
        segments = innermost(spans)
        for plane in devices:
            idle.update(split_idle(device_gaps(plane, lo, hi), segments))
    scale = 1e-9 / max(len(devices), 1)
    return {"self_s": {k: v * 1e-9 for k, v in self_ns.items()},
            "idle_s": {k: v * scale for k, v in idle.items()},
            "event_cover": event_cover(spans, lo, hi)}


def per_update_ms(program: Dict, updates: int) -> Dict[str, Optional[float]]:
    """Each cost of :data:`PER_UPDATE` in ms per committed update: None
    where one of its spans is absent from the window, never 0."""
    self_s = program["self_s"]
    return {name: (sum(self_s[s] for s in spans) / updates * 1e3
                   if updates and all(s in self_s for s in spans) else None)
            for name, spans in PER_UPDATE.items()}
