#!/usr/bin/env python3
"""Run one cell traced and split its window by the program's own spans.

    python3 bench/program_trace.py --workload <cell> --seed <n> \
        [--seconds 8] [--fixture <out.json> --cut-ms 25]

The run is ``bench/run.py``'s traced run (``--trace 1``), and it prints
the same ``window`` and result lines.  Between them a ``program`` line
gives the reduction of ``bench/program_spans.py``: per committed update,
each ``repro.*`` span's self time and the host costs of
``program_spans.PER_UPDATE``; device-idle time split by overlap over the
innermost program span (``idle_s``) beside the benchmark's midpoint
labels (``midpoint_idle_s``, the result line's ``breakdown.idle_gaps``);
the benchmark's own wrapper spans per update; and the share of the
window that ``repro.event`` covers.  ``--fixture`` also writes a
``--cut-ms`` slice of the trace, re-based to the slice's start, in the
plain structure of ``trace_reduce.load_xplane`` (the recorded traces
under ``bench/tests/data`` are such slices).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import program_spans, run, trace_reduce  # noqa: E402

#: a device op's name is cut to this many characters in a fixture
OP_NAME_CHARS = 160


def program_line(trace: dict, updates: int) -> dict:
    """The ``program`` line of a traced window's trace."""
    p = program_spans.reduce(trace)
    return {
        "updates": updates,
        "per_update_ms": program_spans.per_update_ms(p, updates),
        "self_ms_per_update": {k: v / updates * 1e3
                               for k, v in sorted(p["self_s"].items())},
        "idle_s": trace_reduce.top(p["idle_s"], 20),
        "event_cover": p["event_cover"]}


def cut(trace: dict, ms: float) -> dict:
    """A ``ms`` slice of the window, from just before the first
    ``repro.event`` that starts after the window's first tenth: every
    event that overlaps it, re-based to its start, with the window event
    set to the slice; host lines keep only ``bench.*`` and ``repro.*``
    events."""
    lo, hi = program_spans._window(trace)
    starts = sorted(s for plane in trace["planes"]
                    for line in plane["lines"]
                    for n, s, _ in line["events"]
                    if n == program_spans.EVENT and s > lo + (hi - lo) / 10)
    a = (starts[0] if starts else lo) - 10e3
    b = a + ms * 1e6
    planes = []
    for plane in trace["planes"]:
        device = plane["name"].startswith("/device:")
        lines = []
        for line in plane["lines"]:
            if device and line["name"] not in (trace_reduce.OPS_LINE,
                                               trace_reduce.MODULES_LINE):
                continue
            events = [[n[:OP_NAME_CHARS], s - a, d]
                      for n, s, d in line["events"]
                      if s + d > a and s < b and n != trace_reduce.WINDOW
                      and (device or n.startswith(
                          (trace_reduce.SPAN_PREFIX, program_spans.PREFIX)))]
            if events:
                lines.append({"name": line["name"], "events": events})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    host = next(p for p in planes if not p["name"].startswith("/device:"))
    host["lines"][0]["events"].insert(0, [trace_reduce.WINDOW, 0.0, b - a])
    return {"planes": planes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=run.TRACE_SECONDS)
    ap.add_argument("--fixture", default=None)
    ap.add_argument("--cut-ms", type=float, default=25.0)
    args = ap.parse_args(argv)

    cell = run.load_cell(args.workload)
    run.device_info(int(cell.entry["chips"]), require_tpu=True)
    run.enable_cache()
    m = run.measure(args.workload, args.seed, args.seconds, True,
                    t_process=run.T_PROCESS)
    trace = trace_reduce.load_xplane(m.log_dir)
    updates = len(m.rec.update_times)
    line = program_line(trace, updates)
    line["bench_ms_per_update"] = {k: v / updates * 1e3
                                   for k, v in sorted(m.rec.host.items())}
    if args.fixture:
        with open(args.fixture, "w") as f:
            json.dump(cut(trace, args.cut_ms), f, separators=(",", ":"))
    del trace
    result = run.report(m)
    line["midpoint_idle_s"] = result["breakdown"]["idle_gaps"]
    print(json.dumps({"window": result.pop("window")}), flush=True)
    print(json.dumps({"program": line}), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
