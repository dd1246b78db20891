#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip, in one
process: the program's compared numbers on many seeds, and on some of
them the control's and the faults' (see each kind's ``calibrate``).

    python3 bench/tools/calibrate.py --workload <name> --seeds 1,2,3 \\
        --control-seeds 1,2 [--out readings.jsonl]

Prints one JSON line per seed.  Benchmark runs never call this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run as R            # noqa: E402
from bench.lib import device as DV    # noqa: E402
from bench.lib import spec as SP      # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = SP.load_cell(args.workload)
    jax = R.configure_jax()
    try:
        DV.require_chips(jax, cell.chips)
    except DV.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    kind = SP.load_kind(cell.kind, cell.root)
    control = {int(s) for s in args.control_seeds.split(",") if s}
    out = open(args.out, "a") if args.out else None
    for s in (int(x) for x in args.seeds.split(",")):
        res = kind.calibrate(cell, s, s in control,
                             jax.profiler.TraceAnnotation)
        line = json.dumps({"workload": cell.name, "seed": s, **res})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
