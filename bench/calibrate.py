#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness numbers are set from.

    python bench/calibrate.py --workload <cell> --seeds 12 --controls 3 \
        --seconds 30 [--matmul highest] [--out chiprun_out/cal_<cell>.json]

In one process it runs the cell as ``bench/run.py`` does, on ``--seeds``
seeds, and compares each with the reference: the largest of these
readings is the lower end of each limit.  On the first ``--controls``
seeds it also puts in the program's place:

* ``control``: the reference one precision below the configuration's
  (``high``, three bf16 passes, where it states float32 at ``highest``;
  bfloat16 throughout for other float32);
* ``bf16`` and ``high``: both of those, whichever is the control;
* ``half``: the reference training only the first half of each round's
  clients, Eq. 4 over those (half of the batch left out);
* ``unchanged``: a step that returns the server state it was given.

The smallest of those readings bounds each limit from above.  Every
candidate number of ``bench/compare.py`` is reported, with the readings
(per step and per leaf) it comes from.  ``--matmul`` runs the program's
matmuls at another precision than the configuration states (a witness).
This is a tool for whoever sets the limits; the benchmark's runs never
run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run  # noqa: E402


KINDS = ("bf16", "high", "half", "unchanged")


def control_kind(m) -> str:
    """The control of the cell's configuration."""
    highest = m.cell.config.get("matmul_precision") == "highest"
    return "high" if highest else "bf16"


def planted(m, kind: str, detail: bool = False):
    """The compared numbers with ``kind`` in the program's place (and
    the readings they come from, with ``detail``)."""
    import jax.numpy as jnp
    from bench.fedat_ref import HIGH
    rec = m.rec
    if kind == "control":
        kind = control_kind(m)
    if kind == "unchanged":
        start = run.reference(m)
        out = run.readings(m, start.state(),
                           [start.w_global for _ in rec.steps])
    else:
        other = {"bf16": lambda: run.reference(m, jnp.bfloat16, None),
                 "high": lambda: run.reference(m, precision=HIGH),
                 "half": lambda: run.reference(m)}[kind]()
        tiers = run.follow(m, other, half=kind == "half")
        out = run.readings(m, other.state(), tiers)
    numbers = run.compare.numbers(out)
    return (numbers, out) if detail else numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--first-seed", type=int, default=3_000_000_011)
    ap.add_argument("--matmul", choices=("default", "high", "highest"))
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    cell = run.load_cell(args.workload)
    run.device_info(int(cell.entry["chips"]), require_tpu=True)
    run.enable_cache()

    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        m = run.measure(args.workload, seed, args.seconds, False,
                        matmul=args.matmul)
        readings = run.readings(m)
        row = {"seed": seed, "updates": len(m.rec.update_times),
               "program": run.compare.numbers(readings)}
        detail = {"program": readings}
        if i < args.controls:
            for kind in KINDS:
                row[kind], detail[kind] = planted(m, kind, detail=True)
        print(json.dumps(row), flush=True)
        row["readings"] = detail
        rows.append(row)

    names = sorted({k for r in rows for k in r["program"]})
    summary = {"lower": {k: max(r["program"][k] for r in rows)
                         for k in names}}
    for kind in KINDS:
        summary[kind] = {k: min(r[kind][k] for r in rows if kind in r)
                         for k in names if any(kind in r for r in rows)}
    print(json.dumps({"summary": summary}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "matmul": args.matmul,
                       "rows": rows, "summary": summary}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
